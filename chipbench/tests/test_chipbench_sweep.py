"""The sweep's rule for the knee, and the rate it writes into a mix."""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import sweep                             # noqa: E402


def _row(rate, end, shed=0):
    return {"rate_rps": rate, "backlog_start_mid_end": [0, 0, end],
            "shed": shed}


def test_knee_is_the_highest_sustained_rate_below_one_that_is_not():
    rows = [_row(1.6, 40), _row(0.8, 0), _row(1.2, 3), _row(1.4, 12)]
    assert sweep.knee(rows) == 1.2
    assert sweep.knee([_row(1.0, 0), _row(1.2, 2, shed=1)]) == 1.0


def test_no_knee_where_the_sweep_does_not_bracket_it():
    assert sweep.knee([_row(4.0, 41), _row(6.0, 119)]) is None
    assert sweep.knee([_row(1.0, 0), _row(2.0, 1)]) is None


def test_pick_writes_factor_times_knee(tmp_path):
    mix = {"about": "x", "rate_rps": 3.2, "warm_s": 30}
    (tmp_path / "m.json").write_text(json.dumps(mix, indent=2))
    assert sweep.pick(tmp_path, 1.3, {"m": 0.8}) == {"m": 1.04}
    assert json.loads((tmp_path / "m.json").read_text()) == dict(
        mix, rate_rps=1.04)
