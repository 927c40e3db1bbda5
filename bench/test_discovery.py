"""A configuration, a traffic mix and a per-layer metric added as new
files are found by name, with no edit to any file already there."""
import json
import os
import shutil

from bench import spec as bspec


def test_new_files_are_found_by_name(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(bspec.BENCH_DIR, root,
                    ignore=shutil.ignore_patterns("__pycache__", "traces"))
    bench = bspec.load_benchmark()
    cfg = bspec.load_config("starcoder2-3b.l1")
    cfg["name"] = "newmodel.l2"
    (root / "configs" / "newmodel.l2.json").write_text(json.dumps(cfg))
    traffic = bspec.load_traffic("decode-long")
    traffic["name"] = "new-mix"
    (root / "traffic" / "new-mix.json").write_text(json.dumps(traffic))
    (root / "limits" / "newmodel.new-mix.json").write_text(json.dumps(
        {"logp_gap": 1, "loss_gap": 1, "grad_gap": 1, "change_gap": 1}))
    (root / "metrics" / "new_counter.py").write_text(
        "def read(ctx):\n    return ctx.answer\n")
    bench["workloads"].append({"name": "newmodel.new-mix",
                               "config": "newmodel.l2",
                               "traffic": "new-mix", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new_counter", "unit": "1",
                               "better": "higher", "source": "program_counter",
                               "layer": "controller",
                               "moves": "trained_tokens_per_s",
                               "workloads": ["newmodel.new-mix"]})
    cell = bspec.load_cell("newmodel.new-mix", bench, root=str(root))
    assert cell.config["name"] == "newmodel.l2"
    assert cell.traffic["name"] == "new-mix"
    names = [m["name"] for m in cell.metrics(trace=True)]
    assert "new_counter" in names
    reader = bspec.metric_reader("new_counter", str(root))
    assert reader(type("Ctx", (), {"answer": 42})) == 42
    # the cells already there do not see the new metric
    old = bspec.load_cell("sc2-3b.decode-long", bench, root=str(root))
    assert "new_counter" not in [m["name"] for m in old.metrics(trace=True)]
    assert os.path.exists(root / "configs" / "starcoder2-3b.l1.json")
