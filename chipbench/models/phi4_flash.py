"""The system under test for a Phi-4-mini-flash configuration: ``Phi4FlashLM`` served
through ``ModelRegistry.register(..., generate=...)`` and the one ``DecodeEngine``, driven
in process through ``version.batcher.submit_generate`` with the benchmark's own weights
from the seed (the same arrays the plain reference makes)."""
from chipbench.models import decoder
from chipbench.reference import phi4_flash as ref


def program_layout(s):
    """The reference's weights in the program's two-level layout. No copy but of
    ``A_log``, which the program holds ``[d_state, d_inner]`` (327 KB a layer)."""
    p = {"embed": {"tok": s["embed"]}, "head": {"ln_g": s["final_g"], "ln_b": s["final_b"]}}
    for i, layer in enumerate(s["layers"]):
        p[f"layer_{i}"] = dict(layer, A_log=layer["A_log"].T) if "A_log" in layer else layer
    return p


class Program(decoder.Program):
    def __init__(self, cfg, mix, seed, chips):
        from deeplearning4j_tpu.models.phi4_flash import Phi4FlashConfig, Phi4FlashLM
        from deeplearning4j_tpu.serving.registry import ModelRegistry
        model = Phi4FlashLM(Phi4FlashConfig.from_published(
            cfg, max_len=cfg["max_len"], eos_id=cfg["vocab_size"]))
        model.params = program_layout(ref.make_params(cfg, seed))
        eng = dict(cfg["engine"])
        eng["prompt_buckets"] = tuple(eng["prompt_buckets"])
        eng["decode_buckets"] = tuple(eng["decode_buckets"])
        self.registry = ModelRegistry()
        self.version = self.registry.register("lm", model, generate=eng)
        self.pool = self.version.batcher.engine.pool

    def close(self):
        self.pool.state = {}
        super().close()


def build(cfg, mix, seed, chips):
    return Program(cfg, mix, seed, chips)
