"""Operations of a bottleneck ResNet, from its sizes alone."""


def conv_sites(cfg):
    """Every convolution as (name, out_h, out_w, kh, kw, cin, cout), and the
    spatial size and channels entering the head."""
    h = w = None
    sites = []

    def conv(name, hw, k, s, cin, cout):
        out = -(-hw // s)
        sites.append((name, out, out, k, k, cin, cout))
        return out

    hw = conv("stem", cfg["height"], 7, 2, cfg["channels"], 64)
    hw = -(-hw // 2)                       # 3x3/2 max pool, SAME
    cin = 64
    for si, (blocks, width) in enumerate(cfg["stages"]):
        for bi in range(blocks):
            s = 2 if (bi == 0 and si > 0) else 1
            name = f"s{si}b{bi}"
            if bi == 0:
                conv(f"{name}_sc", hw, 1, s, cin, 4 * width)
            hw_a = conv(f"{name}_a", hw, 1, s, cin, width)
            conv(f"{name}_b", hw_a, 3, 1, width, width)
            conv(f"{name}_c", hw_a, 1, 1, width, 4 * width)
            hw, cin = hw_a, 4 * width
    return sites, cin


def forward_flops(cfg):
    """Multiply-adds x 2 of the convolutions and the classifier, one sample."""
    sites, feat = conv_sites(cfg)
    conv = sum(2 * oh * ow * kh * kw * ci * co for _, oh, ow, kh, kw, ci, co in sites)
    return conv + 2 * feat * cfg["num_classes"]


def train_flops(cfg):
    """Forward plus the two backward products of every layer: 3 x forward. The
    stem's input gradient is never needed, so it is taken off. Batch norm,
    activations, pooling, loss and the updater are not counted."""
    sites, _ = conv_sites(cfg)
    _, oh, ow, kh, kw, ci, co = sites[0]
    return 3 * forward_flops(cfg) - 2 * oh * ow * kh * kw * ci * co

