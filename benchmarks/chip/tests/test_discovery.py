"""A configuration, a traffic mix and a per-layer metric added as files,
with entries in BENCHMARK.json, are found by name: a later cell needs
no edit to a file that is already there."""
import json

from benchmarks.chip import spec


def test_new_files_are_found_by_name(tmp_path):
    harness = tmp_path / "benchmarks" / "chip"
    for sub in ("configs", "traffic", "metrics"):
        (harness / sub).mkdir(parents=True)
    (harness / "configs" / "tiny-static.json").write_text(
        json.dumps({"engine": {"side": "V"}}))
    (harness / "traffic" / "tiny-mix.json").write_text(json.dumps(
        {"kind": "static", "route": "dense",
         "graph": {"family": "powerlaw", "n_u": 64, "n_v": 32,
                   "m_target": 200, "alpha_u": 2.0, "alpha_v": 2.0,
                   "structure_seed": 0}}))
    (harness / "metrics" / "twice_sweeps.static.py").write_text(
        "def read(ctx):\n"
        "    runs = ctx.get('decompositions')\n"
        "    return 2 * runs[0]['rho_fd'] if runs else None\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny-static",
                     "file": "benchmarks/chip/configs/tiny-static.json"}],
        "workloads": [{"name": "tiny", "config": "tiny-static",
                       "traffic": "tiny-mix", "chips": 1}],
        "end_to_end": [{"name": "decompose_s", "unit": "s"},
                       {"name": "other_s", "unit": "s",
                        "workloads": ["elsewhere"]}],
        "per_layer": [{"name": "twice_sweeps.static", "unit": "count",
                       "workloads": ["tiny"]}]}))

    cell = spec.load_cell("tiny", tmp_path)
    assert cell.config == {"engine": {"side": "V"}}
    assert cell.traffic["graph"]["n_u"] == 64
    assert [m["name"] for m in cell.end_to_end] == ["decompose_s"]
    assert spec.kind_module(cell.traffic["kind"]).__name__.endswith("static")
    got = spec.read_metrics(cell.per_layer,
                            {"decompositions": [{"rho_fd": 21}]},
                            base=harness)
    assert got == {"twice_sweeps.static": {"value": 42, "unit": "count"}}
    # a reader that finds nothing to read leaves its metric out
    assert spec.read_metrics(cell.per_layer, {}, base=harness) == {}


def test_every_named_part_of_the_benchmark_exists():
    root = spec.HERE.parents[1]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], root)
        spec.kind_module(cell.traffic["kind"])
        for m in cell.per_layer:
            spec.reader(m["name"])
