"""Golden end-to-end run over the committed price fixture.

Reproduces the reference's observable flow (SURVEY.md §3.1-3.4): load the
6,046-row price CSV, filter 1992-01-01..2015-01-01 (the driver's requested
range, ShareTradeHelper.scala:23), train 10 workers over the full episode,
and report the avg/std portfolio aggregation (ShareTradeHelper.scala:46) —
through the public CLI, no test harness shortcuts. The fixture is a frozen
generated series (tools/make_fixture.py), not the reference's data file.
"""

import json
import os

import numpy as np
import pytest

from sharetrade_tpu import cli
from sharetrade_tpu.data.service import PriceDataService
from sharetrade_tpu.config import FrameworkConfig

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "data", "fixtures", "msft-synth-prices.csv")
HIST_FIXTURE = os.path.join(os.path.dirname(FIXTURE), "msft-hist-shaped.csv")
START, END = "1992-01-01", "2015-01-01"
FIXTURE_ROWS = 6046        # full file (reference fixture's line count)
RANGE_ROWS = 5857          # rows inside the driver's requested date range
WINDOW = 201


def _train_args(tmp_path, tag):
    return [
        "train", "--symbol", "MSFT", "--start", START, "--end", END,
        "--set", f"data.csv_path={FIXTURE}",
        "--set", f"data.journal_dir={tmp_path}/journal-{tag}",
        "--set", f"runtime.checkpoint_dir={tmp_path}/ckpts-{tag}",
        "--set", "runtime.chunk_steps=512",
    ]


class TestDataLayerGolden:
    def test_fixture_loads_and_filters(self, tmp_path):
        cfg = FrameworkConfig()
        cfg.data.csv_path = FIXTURE
        cfg.data.journal_dir = str(tmp_path / "journal")
        service = PriceDataService(config=cfg.data)
        full = service.request("MSFT")
        assert len(full.series) == FIXTURE_ROWS
        ranged = service.request("MSFT", START, END)
        assert len(ranged.series) == RANGE_ROWS
        assert str(ranged.series.dates[0]) >= START
        assert str(ranged.series.dates[-1]) <= END
        service.close()

    def test_query_subcommand(self, tmp_path, capsys):
        rc = cli.main(["query", "--symbol", "MSFT", "--start", START,
                       "--end", END,
                       "--set", f"data.csv_path={FIXTURE}",
                       "--set", f"data.journal_dir={tmp_path}/journal"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out == {"symbol": "MSFT", "rows": RANGE_ROWS,
                       "first": "1992-07-22", "last": "2015-01-01"}


@pytest.mark.slow
class TestEndToEndGolden:
    def _run(self, tmp_path, capsys, tag):
        rc = cli.main(_train_args(tmp_path, tag))
        assert rc == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    def test_reference_flow_and_determinism(self, tmp_path, capsys):
        result = self._run(tmp_path, capsys, "a")
        # The full episode ran: range rows minus the observation window.
        assert result["env_steps"] == RANGE_ROWS - WINDOW
        assert result["updates"] == RANGE_ROWS - WINDOW
        assert np.isfinite(result["avg_portfolio"])
        assert result["avg_portfolio"] > 0
        assert np.isfinite(result["std_portfolio"])
        assert result["restarts"] == 0
        # Determinism: an identical fresh run reproduces the aggregation
        # bit-for-bit (seeded RNG end to end; no host-side nondeterminism).
        again = self._run(tmp_path, capsys, "b")
        assert again["avg_portfolio"] == result["avg_portfolio"]
        assert again["std_portfolio"] == result["std_portfolio"]

    def test_historical_shaped_data_trains(self, tmp_path, capsys):
        """The reference replays 23 years of REAL market dynamics every run
        (MSFT-stock-prices-revised.txt); the synthetic-walk fixture can't
        represent that regime. msft-hist-shaped.csv is a committed
        reconstruction of the real trajectory's documented milestones
        (tools/make_historical_fixture.py — dot-com run-up/crash, flat
        decade, GFC drawdown, recovery, a trading calendar with gaps), and
        the golden CLI flow must train over it end to end."""
        prices = np.array([float(l.split(",")[0])
                           for l in open(HIST_FIXTURE)])
        dates = [l.split(",")[1].strip() for l in open(HIST_FIXTURE)]
        # The features the walk lacks, asserted so the fixture can't quietly
        # regress into another featureless series:
        assert prices.max() / prices.min() > 10.0     # order-of-magnitude drift
        peak_to_trough = 1.0 - prices[np.argmax(prices):].min() / prices.max()
        assert peak_to_trough > 0.5                   # a real crash
        gaps = np.diff([np.datetime64(d) for d in dates]).astype(int)
        assert (gaps > 1).any() and (gaps >= 3).any()  # holidays + weekends

        rc = cli.main([
            "train", "--symbol", "MSFT", "--start", START, "--end", END,
            "--set", f"data.csv_path={HIST_FIXTURE}",
            "--set", f"data.journal_dir={tmp_path}/journal-hist",
            "--set", f"runtime.checkpoint_dir={tmp_path}/ckpts-hist",
            "--set", "runtime.chunk_steps=512",
        ])
        assert rc == 0
        result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert result["env_steps"] == len(prices) - WINDOW
        assert np.isfinite(result["avg_portfolio"])
        assert result["avg_portfolio"] > 0
        assert result["restarts"] == 0
        # Every summary names the device its numbers belong to.
        assert result["device"] == {"platform": "cpu", "device_kind": "cpu",
                                    "count": 8}

    def test_resume_completes_consistently(self, tmp_path, capsys):
        """Train to completion, then --resume from the final checkpoint:
        the resumed run restores params/opt/RNG/env cursor and reports the
        same aggregation (the reference's stubbed saveSnapshot made real,
        QDecisionPolicyActor.scala:74,91-93)."""
        result = self._run(tmp_path, capsys, "c")
        rc = cli.main(_train_args(tmp_path, "c") + ["--resume"])
        assert rc == 0
        resumed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        # The checkpoint holds the completed episode: nothing left to train,
        # and the portfolio aggregation is preserved across the restore.
        assert resumed["avg_portfolio"] == pytest.approx(
            result["avg_portfolio"], rel=1e-6)
