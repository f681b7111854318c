"""Over the spans of one name, the mean or the maximum of one ``args`` value over
another, in %: a count the program had in its hand where the work happened. The ring
holds the whole run, so this reads the whole timed window and not the part of it that
was profiled: a peak over a seventh of the window is not the window's peak."""
from chipbench.readers import ring


def read(view, span, num, den, stat="mean", required=False):
    window = (view["records"]["t0"], view["records"]["t_end"])
    found = ring.inside(view, span, window, required)
    ratios = [a[num] / a[den] for _, _, a in found or () if a.get(den)]
    if not ratios:
        return None
    return 100.0 * (max(ratios) if stat == "max" else sum(ratios) / len(ratios))
