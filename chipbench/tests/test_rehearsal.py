"""A CPU rehearsal of a whole run's control flow at a tiny size: build,
warm, ramp, window, trace, check.  It reports no device metric."""
import json

import pytest

from chipbench import run as entry
from chipbench.tests import _tiny


@pytest.mark.parametrize("traffic,trace", [("prefill_sat", False),
                                           ("chat", True)])
def test_a_sound_run_is_correct_and_reports_no_device_metric(traffic,
                                                              trace):
    r = _tiny.run(traffic, trace=trace)
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"] == {}                    # off a chip: none at all
    assert "breakdown" not in r
    assert r["device"]["platform"] == "cpu"
    checks = r["checks"]
    assert list(r)[-1] == "checks"
    assert checks["compared_tokens"]["value"] >= 48
    assert checks["mean_gap"]["value"] <= checks["mean_gap"]["limit"]


def test_the_entry_point_refuses_a_host_without_a_chip(capsys,
                                                       monkeypatch):
    from chipbench import bench
    # the persistent cache would outlive this test in the worker process
    monkeypatch.setattr(bench, "enable_compile_cache", lambda root: root)
    rc = entry.main(["--workload", _tiny.cell("prefill_sat").name,
                     "--seed", "1", "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "needs a TPU" in err
    with pytest.raises(json.JSONDecodeError):
        json.loads(out or "x")
