"""The checks of the benchmark's files, each a function of a root: a
checkout's, or a copy to which a test has added a cell or a metric.

A root holds BENCHMARK.json and benchmark/ with its data directories
(workloads/, configs/, goldens/, traffic/, layer_metrics/) and the two
that hold a file a name leads to (modes/, readers/). The tests call the
checks on the repository; test_additions.py calls them on a copy it has
added to, which is what keeps them from pinning the lists of the day.

**Which cell reports which metric** has one definition here,
``cells_reporting``; ``run.py::layer_metrics`` is its counterpart for a
run. A check fails by AssertionError, with the name it failed on.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import xplane  # noqa: E402

# what of a metric's file its BENCHMARK.json entry repeats
SHARED = ("layer", "unit", "moves", "source", "better")

# PR 35's six, which split `setup_s`, in their order in BENCHMARK.json
SETUP = {"setup_pre_s": "Process + backend", "load_trace_s": "Compile + cache",
         "load_lower_s": "Compile + cache", "load_cache_read_s": "Compile + cache",
         "load_compile_s": "Compile + cache", "load_union_s": "Compile + cache"}

# PR 24's nine, which read the program's own scopes, spans and counters:
# a stage metric for every stage a cell runs (`exchange` has none while
# no cell crosses chips) and for the ops under no stage, and three more
STAGE_METRICS = {f"{stage}_s_per_mstate": stage
                 for stage in xplane.STAGES if stage != "exchange"}
STAGE_METRICS["unscoped_s_per_mstate"] = None
TRACING = [*STAGE_METRICS, "wave_idle_ms", "host_share", "programs_loaded"]


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def names(d, root=ROOT):
    return sorted(f[:-5] for f in os.listdir(os.path.join(root, "benchmark", d))
                  if f.endswith(".json"))


def layer_metric_files(root=ROOT):
    return {name: load(root, "benchmark", "layer_metrics", f"{name}.json")
            for name in names("layer_metrics", root)}


def cells(root):
    """BENCHMARK.json's cells, in its order."""
    return [w["name"] for w in load(root, "BENCHMARK.json")["workloads"]]


def cells_reporting(root, metric):
    """The rule: BENCHMARK.json's cells, in its order, whose own file
    lists ``metric`` under ``per_layer`` or which the metric's file lists
    under ``workloads``."""
    named = load(root, "benchmark", "layer_metrics", f"{metric}.json").get("workloads", ())
    return [cell for cell in cells(root) if cell in named
            or metric in load(root, "benchmark", "workloads", f"{cell}.json")["per_layer"]]


def listed_cells(root, entry):
    """The cells of a metric's BENCHMARK.json entry: no list, every cell."""
    return entry.get("workloads", cells(root))


def check_cell(root, cell):
    """A cell's file leads to its configuration, golden, traffic mix and
    mode, and says of itself what BENCHMARK.json says of it."""
    bench_dir = os.path.join(root, "benchmark")
    spec = load(bench_dir, "workloads", f"{cell}.json")
    config = load(bench_dir, "configs", spec["config"], "config.json")
    assert os.path.exists(os.path.join(bench_dir, "configs", spec["config"], config["cfg"]))
    traffic = load(bench_dir, "traffic", f"{spec['traffic']}.json")
    golden = load(bench_dir, "goldens", f"{spec['config']}.json")
    for depth in (traffic["max_depth"], traffic["warmup_depth"]):
        assert str(depth) in golden["totals"] and len(golden["depth_counts"]) > depth
        assert depth <= golden["independent_to_depth"]
    assert os.path.exists(os.path.join(bench_dir, "modes", f"{traffic['mode']}.py"))
    assert golden["msg_slots"] == spec["engine_params"]["msg_slots"]
    specs = layer_metric_files(root)
    assert set(spec["per_layer"]) <= set(specs), f"{cell} lists a metric that has no file"
    layer = [name for name in specs if cell in cells_reporting(root, name)]
    for name in layer:
        assert specs[name]["moves"] in spec["end_to_end"], (
            f"{name} moves a metric that {cell} does not report")

    bench = load(root, "BENCHMARK.json")
    (entry,) = [w for w in bench["workloads"] if w["name"] == cell]
    assert {k: spec[k] for k in ("config", "traffic", "chips", "why")} == {
        k: entry[k] for k in ("config", "traffic", "chips", "why")}
    for kind, reported in (("end_to_end", spec["end_to_end"]), ("per_layer", layer)):
        listed = {m["name"] for m in bench[kind] if cell in listed_cells(root, m)}
        assert set(reported) == listed, f"{cell}: {kind} differs from BENCHMARK.json's lists"


def check_metric(root, metric):
    """A per-layer metric's file and its BENCHMARK.json entry say the
    same, and the entry's cells are those that report it."""
    spec = load(root, "benchmark", "layer_metrics", f"{metric}.json")
    assert spec["name"] == metric
    assert os.path.exists(os.path.join(
        root, "benchmark", "readers", f"{spec['reduce']['kind']}.py")), f"{metric} has no reader"
    assert set(spec.get("workloads", ())) <= set(cells(root)), (
        f"{metric} names a cell BENCHMARK.json does not have")
    (entry,) = [m for m in load(root, "BENCHMARK.json")["per_layer"] if m["name"] == metric]
    for key in SHARED:
        assert spec[key] == entry[key], f"{metric}: {key} differs from BENCHMARK.json's"
    reporting = cells_reporting(root, metric)
    assert listed_cells(root, entry) == reporting, (
        f"{metric}: BENCHMARK.json's cells are not those that report it")
    for cell in reporting:
        assert spec["moves"] in load(root, "benchmark", "workloads", f"{cell}.json")["end_to_end"], (
            f"{cell} does not report what {metric} moves")


def check_listing(root):
    """Nothing prepared and unlisted: every cell, traffic mix,
    configuration and per-layer metric file is one BENCHMARK.json names."""
    bench = load(root, "BENCHMARK.json")
    assert sorted(cells(root)) == names("workloads", root)
    assert sorted({w["traffic"] for w in bench["workloads"]}) == names("traffic", root)
    assert sorted(m["name"] for m in bench["per_layer"]) == names("layer_metrics", root), (
        "per-layer metric files and BENCHMARK.json's per_layer differ")
    configs = sorted(c["name"] for c in bench["configs"])
    assert configs == sorted(os.listdir(os.path.join(root, "benchmark", "configs")))
    assert configs == names("goldens", root)
    for c in bench["configs"]:
        config = load(root, c["file"])
        assert (config["source"], config["reduced"]) == (c["source"], c["reduced"])
        assert config["guarantees"]


def check_setup_metrics(root):
    """The six that split `setup_s`: read by `stat`, in their layers, and
    reported by every cell, since every cell reports `setup_s`."""
    specs = layer_metric_files(root)
    listed = {m["name"]: m for m in load(root, "BENCHMARK.json")["per_layer"]}
    assert [name for name in listed if name in SETUP] == list(SETUP)  # the files' order
    for name, layer in SETUP.items():
        spec = specs[name]
        assert spec["reduce"] == {"kind": "stat", "name": name} and spec["name"] == name
        want = {"layer": layer, "unit": "s", "better": "lower", "moves": "setup_s",
                "source": "program_counter"}
        assert {k: spec[k] for k in want} == want == {k: listed[name][k] for k in want}
        assert set(listed[name]) == {"name", "workloads", *want}  # and no other key
        assert cells_reporting(root, name) == cells(root), f"{name} is not reported by every cell"
    for cell in cells(root):  # every cell reports what they move
        assert "setup_s" in load(root, "benchmark", "workloads", f"{cell}.json")["end_to_end"]


def check_tracing_metrics(root):
    """The nine are files, each holds to the rule, and their layers are
    PERF.md section 3's rows."""
    specs = layer_metric_files(root)
    assert set(TRACING) <= set(specs)
    for name in TRACING:
        check_metric(root, name)
    assert {specs[name]["layer"] for name in TRACING} == {
        "Stages in a chunk", "Host wave loop", "Compile + cache"}


def check_stage_metrics(root):
    """Every stage a cell runs has its metric file, read by `scope_time`
    under that stage's scope; other files read by `scope_time` may be
    there."""
    specs = layer_metric_files(root)
    for name, stage in STAGE_METRICS.items():
        assert name in specs, f"{name}: a stage of xplane.STAGES has no metric file"
        reduce = specs[name]["reduce"]
        assert (reduce["kind"], reduce["scope"]) == ("scope_time", stage), name


def only_appended(old, new) -> bool:
    """``new`` is ``old`` with entries appended and nothing else: every
    dict keeps its keys and its scalar values, every list keeps its items
    as a prefix."""
    if isinstance(old, dict):
        return (isinstance(new, dict) and set(old) == set(new)
                and all(only_appended(old[k], new[k]) for k in old))
    if isinstance(old, list):
        return (isinstance(new, list) and len(new) >= len(old)
                and all(only_appended(a, b) for a, b in zip(old, new)))
    return type(old) is type(new) and old == new
