"""Transfer ledger + profiler capture (telemetry/profiling.py).

The attribution contract: integer-ns arithmetic after one rounding at
ingest (sums exact), host-tax 1.0 on zero-device runs (never NaN),
eager dispatch wall kept out of device_ns, and fleet merge summing raw
cumulative blocks. The capture contract: single-flight, duration cap,
auto-stop, bounded prune-oldest retention, and sequence ids resumed
from the sorted directory listing (never a clock).
"""

from __future__ import annotations

import threading

import pytest

from comfyui_distributed_tpu.telemetry.profiling import (
    D2H,
    H2D,
    HOST_BUCKETS,
    ProfilerCapture,
    STAGE_HOST_BUCKETS,
    TransferLedger,
    _to_ns,
    get_transfer_ledger,
    ledger_if_enabled,
    merge_profiling_blocks,
    peek_transfer_ledger,
    set_transfer_ledger,
)


class FakeClock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# --- ledger -----------------------------------------------------------------


class TestTransferLedger:
    def test_integer_ns_conservation_is_exact(self):
        ledger = TransferLedger()
        # floats that would drift under float summation
        for _ in range(1000):
            ledger.note_host("gather", 0.0001)
            ledger.note_host("encode", 0.0003)
            ledger.note_host("ship", 0.0007)
        totals = ledger.totals()
        assert totals["host_ns"]["gather"] == 1000 * _to_ns(0.0001)
        assert totals["host_total_ns"] == sum(totals["host_ns"].values())

    def test_zero_device_host_tax_is_exactly_one(self):
        ledger = TransferLedger()
        ledger.note_host("gather", 0.5)
        ledger.note_dispatch(0.25, device=False)
        assert ledger.host_tax() == 1.0
        assert ledger.snapshot()["host_tax"] == 1.0

    def test_empty_ledger_host_tax_never_nan(self):
        assert TransferLedger().host_tax() == 1.0

    def test_device_vs_eager_split(self):
        ledger = TransferLedger()
        ledger.note_dispatch(1.0, device=True)
        ledger.note_dispatch(3.0, device=False)
        totals = ledger.totals()
        assert totals["device_ns"] == _NS_1
        assert totals["device_dispatches"] == 1
        assert totals["eager_ns"] == 3 * _NS_1
        assert totals["eager_dispatches"] == 1
        # eager wall never inflates the device denominator
        ledger.note_host("gather", 1.0)
        assert ledger.host_tax() == pytest.approx(0.5)

    def test_host_tax_ratio(self):
        ledger = TransferLedger()
        ledger.note_dispatch(3.0, device=True)
        ledger.note_host("gather", 0.5)
        ledger.note_host("ship", 0.5)
        assert ledger.host_tax() == pytest.approx(1.0 / 4.0)

    def test_unknown_bucket_and_direction_ignored(self):
        ledger = TransferLedger()
        ledger.note_host("blend", 1.0)
        ledger.note_transfer("sideways", 100, 1.0)
        totals = ledger.totals()
        assert totals["host_total_ns"] == 0
        assert totals["transfer"] == {
            H2D: {"bytes": 0, "ns": 0, "count": 0},
            D2H: {"bytes": 0, "ns": 0, "count": 0},
        }

    def test_transfer_accounting(self):
        ledger = TransferLedger()
        ledger.note_transfer(H2D, 1024, 0.001)
        ledger.note_transfer(D2H, 2048, 0.002)
        ledger.note_transfer(D2H, -5)  # negative bytes clamp to 0
        snap = ledger.snapshot()
        assert snap["transfer"][H2D] == {
            "bytes": 1024, "ns": _to_ns(0.001), "count": 1,
        }
        assert snap["transfer"][D2H]["bytes"] == 2048
        assert snap["transfer"][D2H]["count"] == 2

    def test_timed_sync_charges_bucket_on_injected_clock(self):
        clock = FakeClock()
        ledger = TransferLedger(clock=clock)
        with ledger.timed_sync(bucket="encode"):
            clock.advance(0.125)
        assert ledger.host_ns["encode"] == _to_ns(0.125)

    def test_negative_elapsed_clamps_to_zero(self):
        ledger = TransferLedger()
        ledger.note_dispatch(-1.0, device=True)
        ledger.note_host("gather", -1.0)
        assert ledger.device_ns == 0
        assert ledger.host_total_ns() == 0

    def test_thread_safety_exact_under_contention(self):
        ledger = TransferLedger()

        def worker():
            for _ in range(500):
                ledger.note_dispatch(0.001, device=True)
                ledger.note_host("gather", 0.001)
                ledger.note_transfer(D2H, 10)
                ledger.note_tiles(1)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        totals = ledger.totals()
        assert totals["device_dispatches"] == 4000
        assert totals["device_ns"] == 4000 * _to_ns(0.001)
        assert totals["transfer"][D2H]["bytes"] == 40000
        assert totals["tiles"] == 4000

    def test_stage_bucket_map_covers_io_stages_only(self):
        assert STAGE_HOST_BUCKETS == {
            "readback": "gather",
            "encode": "encode",
            "decode": "encode",
            "submit": "ship",
        }
        assert set(STAGE_HOST_BUCKETS.values()) <= set(HOST_BUCKETS)


_NS_1 = _to_ns(1.0)


class TestMergeProfilingBlocks:
    def test_merge_sums_raw_cumulative_blocks(self):
        a = TransferLedger()
        a.note_dispatch(1.0, device=True)
        a.note_host("gather", 0.5)
        a.note_transfer(D2H, 100, 0.01)
        a.note_tiles(3)
        b = TransferLedger()
        b.note_dispatch(2.0, device=True)
        b.note_host("ship", 0.5)
        b.note_transfer(H2D, 50)
        b.note_tiles(2)
        merged = merge_profiling_blocks([a.snapshot("w1"), b.snapshot("w2")])
        assert merged["device_ns"] == 3 * _NS_1
        assert merged["device_dispatches"] == 2
        assert merged["host_total_ns"] == _NS_1
        assert merged["tiles"] == 5
        assert merged["transfer"][D2H]["bytes"] == 100
        assert merged["transfer"][H2D]["bytes"] == 50
        assert merged["host_tax"] == pytest.approx(1.0 / 4.0)

    def test_merge_zero_device_fleet_reads_one(self):
        block = {"device_ns": 0, "host_ns": {"gather": 5}, "tiles": 1}
        assert merge_profiling_blocks([block])["host_tax"] == 1.0

    def test_merge_tolerates_garbage_blocks(self):
        good = TransferLedger()
        good.note_dispatch(1.0, device=True)
        merged = merge_profiling_blocks(
            [None, "nope", {"device_ns": "xyz"}, good.snapshot(), {}]
        )
        assert merged["device_ns"] == _NS_1
        assert merged["device_dispatches"] == 1


class TestGlobals:
    def setup_method(self):
        set_transfer_ledger(None)

    def teardown_method(self):
        set_transfer_ledger(None)

    def test_get_creates_peek_does_not(self):
        assert peek_transfer_ledger() is None
        ledger = get_transfer_ledger()
        assert peek_transfer_ledger() is ledger
        assert get_transfer_ledger() is ledger

    def test_ledger_if_enabled_gates_on_knob(self, monkeypatch):
        from comfyui_distributed_tpu.utils import constants

        monkeypatch.setattr(constants, "PROFILING_ENABLED", False)
        assert ledger_if_enabled() is None
        assert peek_transfer_ledger() is None  # disabled gate allocates nothing
        monkeypatch.setattr(constants, "PROFILING_ENABLED", True)
        assert ledger_if_enabled() is get_transfer_ledger()


# --- capture ----------------------------------------------------------------


class FakeProfiler:
    """Stands in for jax.profiler: records calls, can be told to fail,
    and writes a sentinel file on stop so capture dirs have bytes."""

    def __init__(self):
        self.started: list[str] = []
        self.stopped = 0
        self.fail_start: Exception | None = None
        self.fail_stop: Exception | None = None
        self._dir: str | None = None

    def start_trace(self, path, profiler_options=None):
        if self.fail_start is not None:
            raise self.fail_start
        self.started.append(path)
        self.options = profiler_options
        self._dir = path

    def stop_trace(self):
        if self.fail_stop is not None:
            raise self.fail_stop
        self.stopped += 1
        if self._dir is not None:
            import os

            with open(os.path.join(self._dir, "trace.pb"), "wb") as fh:
                fh.write(b"x" * 64)
            self._dir = None


@pytest.fixture()
def fake_profiler(monkeypatch):
    import jax

    fake = FakeProfiler()
    monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)
    return fake


class TestProfilerCapture:
    def test_start_stop_roundtrip(self, tmp_path, fake_profiler):
        clock = FakeClock()
        capture = ProfilerCapture(str(tmp_path), clock=clock, max_seconds=30)
        started = capture.start(duration_s=5.0, tag="Smoke Run!")
        assert started["started"] is True
        assert started["id"] == "trace-0001-smoke_run_"
        clock.advance(1.5)
        stopped = capture.stop()
        assert stopped["stopped"] is True
        assert stopped["elapsed_s"] == pytest.approx(1.5)
        assert stopped["bytes"] > 0
        assert fake_profiler.stopped == 1
        assert capture.counters["started"] == 1
        assert capture.counters["stopped"] == 1

    def test_single_flight_answers_busy(self, tmp_path, fake_profiler):
        capture = ProfilerCapture(str(tmp_path), clock=FakeClock())
        first = capture.start(duration_s=5.0)
        busy = capture.start(duration_s=5.0)
        assert busy == {
            "started": False, "reason": "busy", "active": first["id"],
        }
        assert capture.counters["busy"] == 1
        assert len(fake_profiler.started) == 1
        capture.stop()

    def test_stop_is_idempotent(self, tmp_path, fake_profiler):
        capture = ProfilerCapture(str(tmp_path), clock=FakeClock())
        assert capture.stop() == {"stopped": False, "reason": "not_running"}
        capture.start(duration_s=5.0)
        capture.stop()
        assert capture.stop()["stopped"] is False
        assert fake_profiler.stopped == 1

    def test_duration_clamped_to_cap(self, tmp_path, fake_profiler):
        capture = ProfilerCapture(
            str(tmp_path), clock=FakeClock(), max_seconds=2.0
        )
        started = capture.start(duration_s=9999.0)
        assert started["duration_s"] == 2.0
        capture.stop()
        assert capture.start(duration_s="nonsense") == {
            "started": False, "reason": "bad_duration",
        }

    def test_auto_stop_fires_and_respects_new_capture(
        self, tmp_path, fake_profiler
    ):
        capture = ProfilerCapture(str(tmp_path), clock=FakeClock())
        started = capture.start(duration_s=5.0)
        capture._auto_stop(started["id"])
        assert capture.counters["auto_stopped"] == 1
        assert fake_profiler.stopped == 1
        # a stale timer for an already-stopped capture does nothing
        second = capture.start(duration_s=5.0)
        capture._auto_stop(started["id"])
        assert capture.counters["auto_stopped"] == 1
        assert capture.status()["active"]["id"] == second["id"]
        capture.stop()

    def test_start_trace_failure_degrades(self, tmp_path, fake_profiler):
        fake_profiler.fail_start = RuntimeError("no backend")
        capture = ProfilerCapture(str(tmp_path), clock=FakeClock())
        result = capture.start(duration_s=1.0)
        assert result["started"] is False
        assert "no backend" in result["reason"]
        assert capture.counters["errors"] == 1
        assert capture.captures() == []  # the empty dir was removed

    def test_retention_prunes_oldest_never_newest(
        self, tmp_path, fake_profiler
    ):
        capture = ProfilerCapture(
            str(tmp_path), clock=FakeClock(), max_captures=2, max_bytes=0
        )
        for _ in range(4):
            capture.start(duration_s=1.0)
            capture.stop()
        ids = [c["id"] for c in capture.captures()]
        assert ids == ["trace-0004-manual", "trace-0003-manual"]

    def test_byte_budget_prunes(self, tmp_path, fake_profiler):
        capture = ProfilerCapture(
            str(tmp_path), clock=FakeClock(), max_captures=100, max_bytes=150
        )
        for _ in range(3):  # 64 bytes each; 3 > 150-byte budget
            capture.start(duration_s=1.0)
            capture.stop()
        ids = [c["id"] for c in capture.captures()]
        assert ids == ["trace-0003-manual", "trace-0002-manual"]

    def test_seq_resumes_from_sorted_listing(self, tmp_path, fake_profiler):
        (tmp_path / "trace-0007-old").mkdir()
        (tmp_path / "not-a-capture").mkdir()
        capture = ProfilerCapture(str(tmp_path), clock=FakeClock())
        started = capture.start(duration_s=1.0)
        assert started["id"] == "trace-0008-manual"
        capture.stop()

    def test_status_reports_active_elapsed(self, tmp_path, fake_profiler):
        clock = FakeClock()
        capture = ProfilerCapture(str(tmp_path), clock=clock)
        assert capture.status()["active"] is None
        capture.start(duration_s=5.0, tag="x")
        clock.advance(2.0)
        status = capture.status()
        assert status["active"]["elapsed_s"] == pytest.approx(2.0)
        capture.stop()


# --- fleet piggyback (wire v3) ---------------------------------------------


class TestFleetPiggyback:
    def test_local_snapshot_carries_profiling_block(self, monkeypatch):
        from comfyui_distributed_tpu.telemetry import fleet

        set_transfer_ledger(None)
        ledger = get_transfer_ledger()
        ledger.note_dispatch(1.0, device=True)
        ledger.note_tiles(2)
        try:
            snap = fleet.local_snapshot(role="worker")
            assert snap["v"] == 3
            block = snap["profiling"]
            assert block["device_ns"] == _NS_1
            assert block["tiles"] == 2
        finally:
            set_transfer_ledger(None)

    def test_rollup_sums_worker_blocks(self):
        from comfyui_distributed_tpu.telemetry.fleet import FleetRegistry

        set_transfer_ledger(None)
        registry = FleetRegistry()
        for worker, ns in (("w1", 1.0), ("w2", 2.0)):
            ledger = TransferLedger()
            ledger.note_dispatch(ns, device=True)
            ledger.note_host("gather", 0.5)
            ledger.note_tiles(1)
            snap = {
                "v": 3,
                "role": "worker",
                "profiling": ledger.snapshot("worker"),
            }
            assert registry.note_snapshot(worker, snap)
        rollup = registry.rollup()
        profiling = rollup["profiling"]
        assert profiling["device_ns"] == 3 * _NS_1
        assert profiling["host_total_ns"] == _NS_1
        assert profiling["tiles"] == 2
        assert profiling["host_tax"] == pytest.approx(1.0 / 4.0)

    def test_old_snapshot_versions_still_accepted(self):
        from comfyui_distributed_tpu.telemetry.fleet import (
            ACCEPTED_SNAPSHOT_VERSIONS,
            FleetRegistry,
        )

        assert set(ACCEPTED_SNAPSHOT_VERSIONS) == {1, 2, 3}
        registry = FleetRegistry()
        assert registry.note_snapshot("w1", {"v": 2, "role": "worker"})
        rollup = registry.rollup()
        # a v2-only fleet merges no blocks; the key stays absent/None
        assert not rollup.get("profiling")


class TestTransferNbytes:
    def test_numpy_and_jax_arrays_answer_real_bytes(self):
        import jax.numpy as jnp
        import numpy as np

        from comfyui_distributed_tpu.telemetry.profiling import transfer_nbytes

        assert transfer_nbytes(np.zeros((4, 4), np.float32)) == 64
        assert transfer_nbytes(jnp.zeros((4, 4), jnp.float32)) == 64

    def test_typed_prng_key_arrays_count_their_backing_buffer(self):
        """jax.random.key arrays raise on .nbytes (extended dtype);
        _place feeds them to the ledger on every mesh dispatch — the
        helper must answer the uint32 backing size, never crash."""
        import jax

        from comfyui_distributed_tpu.telemetry.profiling import transfer_nbytes

        keys = jax.random.split(jax.random.key(0), 4)
        assert transfer_nbytes(keys) == int(
            jax.random.key_data(keys).nbytes
        )

    def test_unanswerable_objects_count_zero(self):
        from comfyui_distributed_tpu.telemetry.profiling import transfer_nbytes

        class Opaque:
            @property
            def nbytes(self):
                raise RuntimeError("no")

        assert transfer_nbytes(object()) == 0
        assert transfer_nbytes(Opaque()) == 0
        assert transfer_nbytes(None) == 0
