"""Each cell end to end at toy size on the CPU, with the Pallas kernels
under the interpreter: set-up, the window through
``ClusterBackend.serve``, every metric the cell reports, and the
comparison that decides ``correct``."""
import json

import pytest

from chipbench import registry
from chipbench.tests import toybench


@pytest.mark.parametrize("cell", sorted(toybench.CELLS))
def test_cell_at_toy_size(cell):
    line, out, err = toybench.run(cell, impl="interpret")
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 24
    want = {m["name"] for m in registry.metrics_for(
        toybench.bench(), "end_to_end", cell)}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert list(line)[-1] == "checks"
    window = json.loads(out.splitlines()[-2])["window"]
    assert window["compiles_in_window"] == 0
    shares = window["realized_defer_shares"]
    assert len(shares) == len(window["completed_per_tier"]) - 1
    if cell == "c2-easy-backlog":
        assert shares == [0.0]
    # the last lines of standard error: each number beside its limit
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert all(" limit " in t for t in tail)


def test_traced_run_reports_host_metrics():
    """On the CPU the trace has no device plane and the chip has no peak:
    the device metrics are left out, the host ones are read."""
    line, _out, _err = toybench.run("c2-hard-backlog", trace=True,
                                    impl="ref")
    assert line["correct"]
    got = set(line["metrics"])
    assert {"control_tick_ms", "batch_fill", "tier0_call_ms",
            "disc_call_ms"} <= got
    assert not got & {"idle_share", "groupnorm_ms", "serve_mfu",
                      "attention_roofline"}
    assert 0 < line["metrics"]["batch_fill"]["value"] <= 100
