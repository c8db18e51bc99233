"""The harness is driven by data: a new configuration, traffic mix and
per-layer metric, all in a directory of their own, make a new cell that
runs with no harness file edited."""

import json
import shutil

from bench import harness

METRIC = '''
"""Counts the engine's admissions in the window."""


def read(ctx):
    n = sum(1 for e in ctx.events if e["kind"] == "admit")
    return float(n) if n else None
'''


def test_new_cell_from_files_alone(tmp_path):
    src = harness.Layout()
    ext = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics"):
        (ext / sub).mkdir(parents=True)
    cfg = src.json("configs", "h2o-danube-3-4b")
    cfg["name"] = "danube-other"
    (ext / "configs" / "danube-other.json").write_text(json.dumps(cfg))
    traffic = src.json("traffic", "chat_short")
    traffic.update(rate=12.0, arrival={"dist": "poisson"},
                   prompt={"dist": "uniform", "lo": 40, "hi": 120})
    (ext / "traffic" / "steady_chat.json").write_text(json.dumps(traffic))
    (ext / "metrics" / "admissions.py").write_text(METRIC)
    bench = json.loads(json.dumps(src.bench))
    bench["configs"].append({"name": "danube-other", "source": "x",
                             "file": "bench/configs/danube-other.json",
                             "reduced": [], "why": "test"})
    bench["workloads"] = [{"name": "danube-other.steady_chat",
                           "config": "danube-other",
                           "traffic": "steady_chat", "chips": 1,
                           "why": "test"}]
    bench["per_layer"].append({"name": "admissions", "unit": "requests",
                               "better": "higher", "source": "program_span",
                               "layer": "engine", "moves": "ttft_p95_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    before = {p: p.read_bytes() for p in harness.BENCH.rglob("*.py")}

    layout = harness.Layout(tmp_path / "BENCHMARK.json",
                            roots=(ext, harness.BENCH))
    result = harness.run("danube-other.steady_chat", 7, 3.0, True,
                         layout=layout, rehearsal=True, log=lambda s: None)
    assert result["metrics"]["admissions"]["value"] >= 1
    assert result["checks"]["requests_compared"]["value"] >= 1
    assert {p: p.read_bytes() for p in harness.BENCH.rglob("*.py")} == before
    shutil.rmtree(ext)
