"""A cell, configuration, mix and per-layer metric added as files alone are
found by name and built, and every entry of BENCHMARK.json has its files."""
import json
import shutil

import numpy as np
import pytest

from bench import model, spec, traffic


def _add_files(root):
    bench = root / "bench"
    for sub in ("cells", "configs", "traffic", "metrics"):
        (bench / sub).mkdir(parents=True)
    cfg = json.loads((spec.BENCH / "configs" / "qwen2-7b.json").read_text())
    cfg["smoke"]["num_hidden_layers"] = 1
    (bench / "configs" / "new-model.json").write_text(json.dumps(cfg))
    mix = json.loads((spec.BENCH / "traffic" / "chat-poisson.json").read_text())
    mix["shared_prefix"] = 0
    (bench / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    (bench / "cells" / "new-model.new-mix.json").write_text(json.dumps(
        {"config": "new-model", "traffic": "new-mix", "chips": 1, "rate": 2.0,
         "why": "added as files", "limits": {"mean_logit_gap": 1.0},
         "smoke_limits": {"mean_logit_gap": 1.0}}))
    (bench / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return ctx['traced'].get('tokens')\n")
    bm = spec.benchmark()
    bm["workloads"].append({"name": "new-model.new-mix", "config": "new-model",
                            "traffic": "new-mix", "chips": 1, "why": "x"})
    bm["per_layer"].append({"name": "new_metric", "unit": "tokens",
                            "better": "higher", "source": "program_counter",
                            "layer": "device", "moves": "ttft_p95_ms",
                            "workloads": ["new-model.new-mix"]})
    for name in ("ttft_p95_ms", "tpot_p95_ms"):
        bm["end_to_end"].append({"name": name, "unit": "ms", "better": "lower",
                                 "bound": 0.1, "source": "host_clock",
                                 "workloads": ["new-model.new-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return bench


def test_new_cell_as_files_alone(tmp_path):
    bench = _add_files(tmp_path)
    c = spec.cell("new-model.new-mix", root=bench)
    assert c["config"]["name"] == "new-model"
    assert c["traffic"]["name"] == "new-mix"
    bm = spec.benchmark(tmp_path)
    assert [m["name"] for m in spec.metrics_for("new-model.new-mix", bm,
                                                False)] == \
        ["setup_s", "ttft_p95_ms", "tpot_p95_ms"]
    assert [m["name"] for m in spec.metrics_for("new-model.new-mix", bm,
                                                True)] == ["new_metric"]
    assert spec.reader("new_metric", root=bench)({"traced": {"tokens": 7}}) == 7
    mix = traffic.merged(c["traffic"], smoke=True)
    d = model.dims(c["config"], smoke=True)
    arr = traffic.generate(mix, 3, 5.0, c["rate"], d["vocab_size"])
    assert arr and all(a.prompt.max() < d["vocab_size"] for a in arr)
    cfg, pol = model.arch(d), model.policy(c["config"], d)
    assert (cfg.n_layers, cfg.d_model, cfg.family) == \
        (1, c["config"]["smoke"]["hidden_size"], "dense")
    knobs = model.engine_knobs(c["traffic"], smoke=True)
    cap = model.capacity(pol, knobs, *traffic.longest(mix))
    assert (cap - pol.n_sink - pol.window) % knobs["pool_block_tokens"] == 0
    assert cap >= sum(traffic.longest(mix)) + knobs["steps_per_sync"]


def test_every_entry_has_its_files():
    bm = spec.benchmark()
    for w in bm["workloads"]:
        c = spec.cell(w["name"])
        assert (c["config"]["name"], c["traffic"]["name"]) == \
            (w["config"], w["traffic"])
        assert c["chips"] == w["chips"]
        assert set(c["limits"]) == {"widest_logit_gap", "mean_logit_gap"}
    for cfg in bm["configs"]:
        data = json.loads((spec.ROOT / cfg["file"]).read_text())
        assert sorted(data["reduced"]) == sorted(cfg["reduced"])
        for k, v in data["published"].items():
            assert k in cfg["reduced"] and data[k] != v
    for m in bm["per_layer"]:
        spec.reader(m["name"])
        moves = [e for e in bm["end_to_end"] if e["name"] == m["moves"]]
        assert moves
        for w in m["workloads"]:
            assert w in moves[0].get("workloads", [w])


@pytest.mark.parametrize("bad", ["../x", "a/b", "", ".hidden"])
def test_names_cannot_leave_their_directory(bad):
    with pytest.raises((ValueError, KeyError)):
        spec.load("cells", bad)
