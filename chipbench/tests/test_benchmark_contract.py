"""Every entry of ``BENCHMARK.json`` against the files the harness finds by its name:
``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``. A cell, a configuration or a
metric is files and entries added at the end of their lists, and no check here depends on
where in its list an entry stands: the last cases append to a copy of the benchmark and
hold the copy to the same checks."""
import importlib.util
import json
import os
import shutil

import pytest

import test_admission_spans as admission
from chipbench import harness

#: the modules a configuration's file names, and the directory each is found in
MODULES = {"driver": "drivers", "builder": "models", "reference": "reference", "counts": "counts"}
PARTS = ("configs", "workloads", "end_to_end", "per_layer")


def bench_of(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load(root, group, name):
    """``chipbench/<group>/<name>.py`` of the tree at ``root``, imported from its file."""
    path = os.path.join(root, "chipbench", group, f"{name}.py")
    assert os.path.isfile(path), f"no {path}"
    spec = importlib.util.spec_from_file_location(f"contract_{group}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check(root, bench, part, name):
    """One entry of ``bench[part]`` against the files under ``root``."""
    entries = [e for e in bench[part] if e["name"] == name]
    assert len(entries) == 1, f"{len(entries)} entries of {part} are named {name}"
    (entry,) = entries
    cells = {w["name"]: w for w in bench["workloads"]}
    assert set(entry.get("workloads", ())) <= set(cells), f"{name} lists what is no cell"
    if part == "configs":
        assert os.path.isfile(os.path.join(root, entry["file"]))
        assert any(w["config"] == name for w in cells.values()), f"no cell runs {name}"
    elif part == "workloads":
        (conf,) = [c for c in bench["configs"] if c["name"] == entry["config"]]
        with open(os.path.join(root, conf["file"])) as f:
            cfg = json.load(f)
        for key, group in MODULES.items():
            load(root, group, cfg[key])
        assert os.path.isfile(os.path.join(root, "chipbench", "traffic", f"{entry['traffic']}.json"))
    elif part == "per_layer":
        with open(os.path.join(root, "chipbench", "metrics", f"{name}.json")) as f:
            spec = json.load(f)
        assert callable(getattr(load(root, "readers", spec["reader"]), "read", None))
        (moved,) = [m for m in bench["end_to_end"] if m["name"] == entry["moves"]]
        reported = set(moved.get("workloads", cells))
        assert set(entry.get("workloads", reported)) <= reported, \
            f"{name} lists a cell that does not report {moved['name']}"


BENCH = bench_of(harness.ROOT)


@pytest.mark.parametrize("part, name", [(p, e["name"]) for p in PARTS for e in BENCH[p]],
                         ids=[f"{p}:{e['name']}" for p in PARTS for e in BENCH[p]])
def test_every_entry_finds_its_files_by_name(part, name):
    check(harness.ROOT, BENCH, part, name)


#: what a later configuration brings: a kernel's roofline, its cell, and its files
NEW_METRIC = {"name": "appended_state_roofline", "unit": "%", "better": "higher",
              "source": "device_trace", "layer": "kernels", "moves": "gen_tok_per_s"}
NEW_SPEC = {"reader": "state_share", "args": {"ops": "appended_state_update"}}
NEW_CELL = {"name": "appended.decode-appended", "config": "appended", "traffic": "decode-appended",
            "chips": 1, "why": "a served cell added as files and entries"}


def appended_copy(dest, with_cell):
    """The benchmark's files copied to ``dest`` with ``NEW_METRIC`` at the end of
    ``per_layer`` and its metric file beside the others; ``with_cell`` adds a served cell
    as a later configuration does, appended to the lists of every metric that reads all
    the served cells, and the new metric lists it alone."""
    shutil.copytree(harness.HERE, os.path.join(dest, "chipbench"),
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    bench = bench_of(harness.ROOT)
    served = admission.served_cells(bench)
    cells = sorted(served)
    if with_cell:
        base = next(c for c in bench["configs"] if c["name"] == "gpt2-large")
        shutil.copy(os.path.join(harness.ROOT, base["file"]),
                    os.path.join(dest, "chipbench", "configs", "appended.json"))
        shutil.copy(os.path.join(harness.HERE, "traffic", "decode-offline.json"),
                    os.path.join(dest, "chipbench", "traffic", "decode-appended.json"))
        bench["configs"].append(dict(base, name="appended", file="chipbench/configs/appended.json"))
        bench["workloads"].append(NEW_CELL)
        for m in bench["end_to_end"] + bench["per_layer"]:
            if set(m.get("workloads", ())) == served:
                m["workloads"].append(NEW_CELL["name"])
        cells = [NEW_CELL["name"]]
    bench["per_layer"].append(dict(NEW_METRIC, workloads=cells))
    with open(os.path.join(dest, "chipbench", "metrics", f"{NEW_METRIC['name']}.json"), "w") as f:
        json.dump(NEW_SPEC, f)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return str(dest)


@pytest.mark.parametrize("with_cell", [False, True], ids=["entry", "entry_and_cell"])
def test_an_entry_appended_at_the_end_of_per_layer_breaks_no_check(tmp_path, with_cell):
    root = appended_copy(tmp_path, with_cell)
    bench = bench_of(root)
    assert bench["per_layer"][-1]["name"] == NEW_METRIC["name"]
    # the accepted entries stand as they were, but for the new cell at the end of their lists
    assert [dict(e, workloads=[w for w in e["workloads"] if w != NEW_CELL["name"]])
            for e in bench["per_layer"][:-1]] == BENCH["per_layer"]
    for part in PARTS:
        for e in bench[part]:
            check(root, bench, part, e["name"])
    admission.check_admission_entries(bench, root)
