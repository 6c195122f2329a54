"""bench.py headline-record contract: the parity field and the core
metric fields.  Pure record assembly — no simulation runs, stays in the
fast tier."""

import json
import sys

sys.path.insert(0, ".")
import bench


def _sample_result():
    return {
        "sims_per_sec": 2.0,
        "compile_s": 10.0,
        "run_s": 2.0,
        "chunk_ms": 20,
    }


class TestHeadlineRecord:
    def test_parity_field_present_and_explicit(self):
        rec = bench._headline(
            4096, 8, _sample_result(), "tpu", "TPU v5 lite",
            {"platform": "tpu"}, None, [], oracle=0.0145,
        )
        par = rec["parity"]
        # stop_when_done preserves the deliverable (done_at) but not the
        # post-done traffic counters — the record must say so explicitly
        assert par["done_at"] is True
        assert par["traffic_counters"] is False
        assert "stop_when_done" in par["note"]

    def test_headline_core_contract(self):
        rec = bench._headline(
            4096, 8, _sample_result(), "tpu", "TPU v5 lite",
            {"platform": "tpu"}, None, [], oracle=0.0145,
        )
        for key in ("metric", "value", "unit", "vs_baseline"):
            assert key in rec, key
        assert rec["metric"] == "handel4096_sims_per_sec_chip"
        assert rec["value"] == 2.0
        assert rec["vs_baseline"] == round(2.0 / 0.0145, 3)
        assert rec["provenance"] == "measured live by this bench run"
        json.dumps(rec)  # one JSON line, serializable
