"""Each cell end to end on the CPU, through the rehearsal path only
(configuration and traffic cut by ``harness.shrink``): the run yields a
complete result, no program compiles inside the window, and the served
streams are the reference's greedy tokens to within bfloat16 rounding."""

import json

import pytest

from bench import harness

CELLS = [w["name"] for w in harness.Layout().bench["workloads"]]
#: At the rehearsal's size (2 layers, width 256) the served tokens'
#: logits lie within a few bfloat16 steps of the reference's best: the
#: program computes in bfloat16 and picks among bfloat16-rounded logits.
CPU_GAP = 0.05


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("traced", [False, True], ids=["e2e", "traced"])
def test_cell_runs_end_to_end_on_the_cpu(workload, traced, capsys):
    lines = []
    result = harness.run(workload, 2 ** 31 + 99, 3.0, traced,
                         rehearsal=True, log=lines.append)
    assert any("compiles inside the window: 0" in s for s in lines)
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    layout = harness.Layout()
    section = "per_layer" if traced else "end_to_end"
    names = {m["name"] for m in layout.metrics(section, workload)}
    got = set(result["metrics"])
    if traced:
        # no device plane on the CPU: only the host-side readers report
        assert got == names & {"queue_wait_ms_p50", "batch_occupancy",
                               "decode_tpot_p95_ms"}
    else:
        assert got == names
        assert result["metrics"]["total_tok_s"]["value"] > 0
    checks = result["checks"]
    assert checks["requests_compared"]["value"] >= \
        checks["requests_compared"]["limit"]
    assert 0 <= checks["max_logit_gap"]["value"] <= CPU_GAP
    harness.emit(result)
    out, err = capsys.readouterr()
    assert list(json.loads(out.splitlines()[-1]))[-1] == "checks"
    assert err.splitlines()[-1].startswith("check requests_compared")


def test_no_result_without_a_tpu():
    with pytest.raises(SystemExit, match="no TPU"):
        harness.run(CELLS[0], 1, 1.0, False)
