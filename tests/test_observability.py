"""Observability plane (ISSUE 17): cross-process fan-in, merged
traces, OpenMetrics exposition, and SLO burn-rate alerting.

Layers under test:
  - the seqlock snapshot lane (SnapshotLane/SnapshotWriter) and its
    crash tolerance — torn publishes are invisible, SIGKILLed writers
    never wedge the parent;
  - TelemetryAggregator re-prefixing worker snapshots under
    proc<h>w<w>/ labels and harvesting trace dumps;
  - the AlertEngine's multi-window burn-rate semantics plus the
    AlertSignal control-plane adapter;
  - MetricsExporter (HTTP endpoint + atomic file fallback) and the
    tools/dash.py parser over its payload;
  - the merged Chrome-trace export with per-process rows;
  - ProcessEnvPool integration: live fan-in, worker-kill repair with
    no stale-pid leak, close-time trace harvest, lane unlink.
"""

import json
import os
import signal
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from torched_impala_tpu.telemetry import (
    AlertEngine,
    FlightRecorder,
    LABEL_RE,
    MetricsExporter,
    Registry,
    SloSpec,
    SnapshotLane,
    SnapshotWriter,
    TelemetryAggregator,
    WorkerTelemetry,
    default_slo_specs,
    export_merged_trace,
    merge_chrome_events,
    metric_name,
    parse_openmetrics,
    proc_label,
    to_openmetrics,
    write_metrics_file,
)
from torched_impala_tpu.telemetry.aggregate import _HEADER
from torched_impala_tpu.telemetry.tracing import validate_chrome_trace


# ---- process labels ------------------------------------------------------


class TestProcLabel:
    def test_label_shape_and_grammar(self):
        assert proc_label(0, 3) == "proc0w3"
        assert proc_label(12, 40) == "proc12w40"
        assert LABEL_RE.match(proc_label(0, 0))
        for bad in ("proc0", "procAw1", "proc0w", "w0proc1", "proc0w1x"):
            assert not LABEL_RE.match(bad), bad

    def test_aggregator_rejects_bad_label(self):
        agg = TelemetryAggregator()
        lane = SnapshotLane(1)
        try:
            with pytest.raises(ValueError):
                agg.attach("worker-1", lane, 0)
        finally:
            lane.close()


# ---- seqlock snapshot lane -----------------------------------------------


class TestSnapshotLane:
    def test_publish_read_roundtrip(self):
        lane = SnapshotLane(2)
        try:
            assert lane.read(0) is None  # never published
            w = SnapshotWriter(lane.descriptor(), 0)
            try:
                assert w.publish({"snapshot": {"telemetry/a/b": 1.5}})
                got = lane.read(0)
                assert got["snapshot"] == {"telemetry/a/b": 1.5}
                # The header pid stamp wins over anything in the body.
                assert got["pid"] == os.getpid()
                assert lane.read(1) is None  # other slot untouched
            finally:
                w.close()
        finally:
            lane.close()

    def test_oversized_payload_refused(self):
        lane = SnapshotLane(1, slot_bytes=256)
        try:
            w = SnapshotWriter(lane.descriptor(), 0)
            try:
                assert not w.publish({"blob": "x" * 512})
                assert lane.read(0) is None  # nothing half-written
                assert w.publish({"ok": 1})
                assert lane.read(0)["ok"] == 1
            finally:
                w.close()
        finally:
            lane.close()

    def test_torn_publish_keeps_last_good(self):
        """A writer dying mid-publish (odd seq left behind — SIGKILL
        between the two header stores) must be invisible: readers keep
        the previous consistent payload forever."""
        lane = SnapshotLane(1)
        try:
            w = SnapshotWriter(lane.descriptor(), 0)
            try:
                assert w.publish({"v": 1})
                assert lane.read(0)["v"] == 1
                # Forge the crash: bump seq to ODD directly in shm,
                # exactly the state a SIGKILL mid-write leaves.
                seq, length, pid = _HEADER.unpack_from(lane._shm.buf, 0)
                _HEADER.pack_into(
                    lane._shm.buf, 0, seq + 1, length, pid
                )
                for _ in range(3):
                    assert lane.read(0)["v"] == 1  # last-good, not torn
            finally:
                w.close()
        finally:
            lane.close()

    def test_garbage_body_keeps_last_good(self):
        lane = SnapshotLane(1)
        try:
            w = SnapshotWriter(lane.descriptor(), 0)
            try:
                assert w.publish({"v": 7})
                assert lane.read(0)["v"] == 7
                # Even seq but a corrupted body (not JSON): fall back.
                seq, _, pid = _HEADER.unpack_from(lane._shm.buf, 0)
                lane._shm.buf[_HEADER.size : _HEADER.size + 4] = b"\xff" * 4
                _HEADER.pack_into(lane._shm.buf, 0, seq + 2, 4, pid)
                assert lane.read(0)["v"] == 7
            finally:
                w.close()
        finally:
            lane.close()

    def test_clear_forgets_slot(self):
        lane = SnapshotLane(1)
        try:
            w = SnapshotWriter(lane.descriptor(), 0)
            try:
                w.publish({"v": 1})
                assert lane.read(0)["v"] == 1
                lane.clear(0)
                assert lane.read(0) is None  # header AND cache dropped
            finally:
                w.close()
        finally:
            lane.close()

    def test_owner_unlinks_segment_on_close(self):
        from multiprocessing import shared_memory

        lane = SnapshotLane(1)
        name = lane.descriptor()[0]
        lane.close()
        with pytest.raises(FileNotFoundError):
            shared_memory.SharedMemory(name=name)


# ---- worker-side telemetry -----------------------------------------------


class TestWorkerTelemetry:
    def test_payload_carries_metrics_and_trace(self):
        lane = SnapshotLane(1)
        try:
            wt = WorkerTelemetry(lane.descriptor(), 0, "proc0w0")
            try:
                t0 = time.monotonic_ns()
                wt.record_step(t0, 2_000_000, "a0u1", 3)
                wt.publish()
                got = lane.read(0)
                snap = got["snapshot"]
                assert snap["telemetry/pool/env_steps"] == 1
                assert snap["telemetry/pool/episode_events"] == 3
                assert snap["telemetry/pool/worker_step_ms_count"] == 1
                recs = [r for r in got["trace"] if r[3] == "pool/worker_step"]
                assert recs and recs[0][5] == {"lid": "a0u1"}
                assert got["label"] == "proc0w0"
            finally:
                wt.close()
        finally:
            lane.close()

    def test_publish_shrinks_trace_tail_to_fit(self):
        """When the full trace tail overflows the slot the publish
        retries with a shrinking tail — metrics always make it out."""
        lane = SnapshotLane(1, slot_bytes=4096)
        try:
            wt = WorkerTelemetry(lane.descriptor(), 0, "proc0w0")
            try:
                t0 = time.monotonic_ns()
                for i in range(500):  # ~40KB of trace >> 4KB slot
                    wt.record_step(t0 + i, 1_000_000, f"a0u{i}", 0)
                wt.publish()
                got = lane.read(0)
                assert got is not None, "publish never landed"
                assert (
                    got["snapshot"]["telemetry/pool/env_steps"] == 500
                )
                assert len(got["trace"]) < 500
            finally:
                wt.close()
        finally:
            lane.close()


# ---- aggregator ----------------------------------------------------------


class TestAggregator:
    def _publish(self, lane, slot, label, snap, pid=None):
        w = SnapshotWriter(lane.descriptor(), slot)
        try:
            payload = {"label": label, "snapshot": snap, "trace": []}
            assert w.publish(payload)
        finally:
            w.close()

    def test_rekeys_worker_snapshots_under_label(self):
        lane = SnapshotLane(2)
        agg = TelemetryAggregator()
        try:
            agg.attach("proc0w0", lane, 0)
            agg.attach("proc0w1", lane, 1)
            self._publish(
                lane, 0, "proc0w0", {"telemetry/pool/env_steps": 5.0}
            )
            self._publish(
                lane, 1, "proc0w1", {"telemetry/pool/env_steps": 9.0}
            )
            out = agg.aggregated_snapshot({"telemetry/local/x": 1.0})
            assert out["telemetry/local/x"] == 1.0
            assert out["telemetry/proc0w0/pool/env_steps"] == 5.0
            assert out["telemetry/proc0w1/pool/env_steps"] == 9.0
            assert agg.worker_pids() == {
                "proc0w0": os.getpid(),
                "proc0w1": os.getpid(),
            }
        finally:
            agg.reset()
            lane.close()

    def test_retired_dumps_bounded(self):
        agg = TelemetryAggregator()
        for i in range(50):
            agg.retire("proc0w0", {"trace": [[i, 0, "i", "a/b", 0, {}]]})
        dumps = agg.trace_dumps()
        assert len(dumps) == 8  # _MAX_RETIRED: crash loops stay bounded
        assert dumps[-1]["trace"][0][0] == 49  # newest kept

    def test_aggregated_keys_pass_label_grammar(self):
        """The re-prefixed keys are exactly what impala-lint's
        agg-prefix rule pins: proc<h>w<w>/<component>/<name>."""
        import re

        lane = SnapshotLane(1)
        agg = TelemetryAggregator()
        try:
            agg.attach("proc0w0", lane, 0)
            self._publish(
                lane,
                0,
                "proc0w0",
                {"telemetry/pool/worker_step_ms_p50": 1.0},
            )
            out = agg.aggregated_snapshot({})
            agg_re = re.compile(
                r"^telemetry/proc\d+w\d+/[a-z][a-z0-9_]*/[a-z][a-z0-9_]*$"
            )
            assert all(agg_re.match(k) for k in out), out
        finally:
            agg.reset()
            lane.close()


# ---- OpenMetrics exposition ----------------------------------------------


class TestOpenMetrics:
    def test_metric_name_mangling(self):
        assert metric_name("telemetry/pool/env_steps") == (
            "impala_pool_env_steps"
        )
        assert metric_name("telemetry/proc0w1/pool/env_steps") == (
            "impala_proc0w1_pool_env_steps"
        )
        assert metric_name("alerts/firing_x") == "impala_alerts_firing_x"

    def test_render_parse_roundtrip_skips_nan(self):
        snap = {
            "telemetry/a/b": 1.5,
            "telemetry/a/unset": float("nan"),
            "telemetry/proc0w0/pool/env_steps": 7.0,
        }
        text = to_openmetrics(snap)
        assert text.endswith("# EOF\n")
        assert "# TYPE impala_a_b gauge" in text
        assert "unset" not in text
        parsed = parse_openmetrics(text)
        assert parsed == {
            "impala_a_b": 1.5,
            "impala_proc0w0_pool_env_steps": 7.0,
        }

    def test_write_metrics_file_atomic(self, tmp_path):
        path = str(tmp_path / "sub" / "metrics.prom")
        write_metrics_file(path, "impala_x 1\n# EOF\n")
        write_metrics_file(path, "impala_x 2\n# EOF\n")
        with open(path) as f:
            assert parse_openmetrics(f.read()) == {"impala_x": 2.0}
        # No tmp litter left behind by the replace protocol.
        litter = [
            p for p in os.listdir(tmp_path / "sub") if p != "metrics.prom"
        ]
        assert litter == []


class TestMetricsExporter:
    def test_http_endpoint_serves_fresh_snapshot(self):
        snap = {"telemetry/pool/env_steps": 1.0}
        exp = MetricsExporter(
            lambda: dict(snap), port=0, registry=Registry()
        ).start()
        try:
            assert exp.port > 0
            url = f"http://127.0.0.1:{exp.port}/metrics"
            with urllib.request.urlopen(url, timeout=10) as resp:
                assert resp.status == 200
                assert "openmetrics" in resp.headers["Content-Type"]
                body = resp.read().decode()
            assert parse_openmetrics(body) == {
                "impala_pool_env_steps": 1.0
            }
            snap["telemetry/pool/env_steps"] = 2.0  # scrape == sample
            with urllib.request.urlopen(url, timeout=10) as resp:
                body = resp.read().decode()
            assert parse_openmetrics(body) == {
                "impala_pool_env_steps": 2.0
            }
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{exp.port}/nope", timeout=10
                )
        finally:
            exp.stop()

    def test_worker_snapshot_crosses_lane_and_scrape_whole(self):
        """A worker-sized payload (64 series, heartbeats, a 256-record
        trace tail) is read back from the lane byte for byte; laid under
        four workers' labels beside the 64 local series, one HTTP scrape
        renders every one of the 128 metrics exactly once."""
        local = {
            f"telemetry/script/series_{i:02d}": float(i) for i in range(64)
        }
        rec = FlightRecorder(capacity=512)
        t_ns = time.monotonic_ns()
        for i in range(256):
            rec.complete("pool/worker_step", t_ns + i, 1000, {"lid": "a0u0"})
        payload = {
            "label": "proc0w0",
            "snapshot": dict(local),
            "heartbeats": {"worker": 12.5},
            "trace": rec.tail(256),
            "thread_names": {},
        }
        sent = json.dumps(payload).encode()
        assert len(sent) > 1_000
        lane = SnapshotLane(1)
        try:
            w = SnapshotWriter(lane.descriptor(), 0)
            try:
                assert w.publish(payload)
                got = lane.read(0)
            finally:
                w.close()
        finally:
            lane.close()
        assert got.pop("pid") == os.getpid()  # the header's stamp
        assert json.dumps(got).encode() == sent

        snap = dict(local)
        for wk in range(4):
            for i in range(16):
                snap[f"telemetry/proc0w{wk}/pool/series_{i:02d}"] = float(i)
        exp = MetricsExporter(
            lambda: dict(snap), port=0, registry=Registry()
        ).start()
        try:
            url = f"http://127.0.0.1:{exp.port}/metrics"
            with urllib.request.urlopen(url, timeout=10) as resp:
                body = resp.read().decode()
        finally:
            exp.stop()
        samples = [
            line.split()[0]
            for line in body.splitlines()
            if line and not line.startswith("#")
        ]
        assert sorted(samples) == sorted(metric_name(k) for k in snap)
        assert len(set(samples)) == len(samples) == 128

    def test_file_fallback_ticks_engine_and_publishes(self, tmp_path):
        """--metrics-file mode: the background tick advances the alert
        engine on a steady cadence AND atomically rewrites the file —
        the sandboxed-run path with no open port."""
        reg = Registry()
        engine = AlertEngine(
            [
                SloSpec(
                    name="probe",
                    key="x/val_ms",
                    objective=10.0,
                    fast_window_s=0.1,
                    slow_window_s=0.2,
                )
            ],
            registry=reg,
            recorder=FlightRecorder(capacity=16),
        )

        def provider():
            snap = dict(reg.snapshot())
            snap["telemetry/x/val_ms"] = 99.0  # sustained breach
            return snap

        path = str(tmp_path / "m.prom")
        exp = MetricsExporter(
            provider,
            path=path,
            interval_s=0.05,
            alert_engine=engine,
            registry=reg,
        ).start()
        try:
            deadline = time.monotonic() + 20
            fired = {}
            while time.monotonic() < deadline:
                if os.path.exists(path):
                    with open(path) as f:
                        fired = parse_openmetrics(f.read())
                    if fired.get("impala_alerts_firing_probe") == 1.0:
                        break
                time.sleep(0.05)
            assert fired.get("impala_alerts_firing_probe") == 1.0, fired
            assert fired.get("impala_export_ticks", 0) >= 1
        finally:
            exp.stop()

    def test_requires_some_output(self):
        with pytest.raises(ValueError):
            MetricsExporter(lambda: {}, registry=Registry())


# ---- SLO burn-rate alerting ----------------------------------------------


def _spec(**kw):
    base = dict(
        name="probe",
        key="x/val_ms",
        objective=10.0,
        budget=0.1,
        fast_window_s=1.0,
        slow_window_s=5.0,
    )
    base.update(kw)
    return SloSpec(**base)


class TestAlertEngine:
    def _engine(self, spec, reg=None):
        return AlertEngine(
            [spec],
            registry=reg if reg is not None else Registry(),
            recorder=FlightRecorder(capacity=64),
        )

    def test_sustained_breach_fires_after_fast_window(self):
        reg = Registry()
        eng = self._engine(_spec(), reg)
        fired_at = None
        t = 0.0
        while t <= 5.0:
            if eng.evaluate({"telemetry/x/val_ms": 50.0}, now=t):
                fired_at = t
                break
            t += 0.25
        # The coverage gate holds the first samples; a real sustained
        # breach fires within ~one fast window, far before the slow one.
        assert fired_at is not None
        assert 1.0 <= fired_at < 2.0, fired_at
        assert eng.firing() == ["probe"]
        snap = reg.snapshot()
        assert snap["telemetry/alerts/firing_probe"] == 1.0
        assert snap["telemetry/alerts/burn_rate_probe"] > 1.0

    def test_brief_spike_does_not_fire(self):
        """The slow window's whole job: a brief spike diluted across a
        window of good samples stays within the error budget (two bad
        of ~20 samples = 10% bad, inside the 20% budget), so the alert
        never pages even though the FAST window saturates."""
        eng = self._engine(_spec(budget=0.2))
        t = 0.0
        while t <= 4.0:  # build up good history
            assert not eng.evaluate({"telemetry/x/val_ms": 1.0}, now=t)
            t += 0.25
        for _ in range(2):  # the spike
            assert not eng.evaluate({"telemetry/x/val_ms": 99.0}, now=t)
            t += 0.25
        while t <= 8.0:
            assert not eng.evaluate({"telemetry/x/val_ms": 1.0}, now=t)
            t += 0.25
        assert eng.firing() == []

    def test_recovery_clears_firing_and_emits_transitions(self):
        rec = FlightRecorder(capacity=64)
        reg = Registry()
        eng = AlertEngine([_spec()], registry=reg, recorder=rec)
        t = 0.0
        while t <= 2.0:
            eng.evaluate({"telemetry/x/val_ms": 50.0}, now=t)
            t += 0.25
        assert eng.firing() == ["probe"]
        while t <= 10.0:
            eng.evaluate({"telemetry/x/val_ms": 1.0}, now=t)
            t += 0.25
        assert eng.firing() == []
        assert reg.snapshot()["telemetry/alerts/firing_probe"] == 0.0
        marks = [
            r for r in rec.tail(64) if r[3] == "telemetry/alert"
        ]
        # One instant per transition: 0->1 and 1->0.
        assert [m[5]["firing"] for m in marks] == [1, 0]

    def test_missing_and_nan_samples_are_skipped(self):
        eng = self._engine(_spec())
        for t in (0.0, 1.0, 2.0, 3.0):
            assert not eng.evaluate({}, now=t)
            assert not eng.evaluate(
                {"telemetry/x/val_ms": float("nan")}, now=t + 0.5
            )
        assert eng.burn_rates() == {"probe": 0.0}

    def test_lower_kind_fires_on_floor_breach(self):
        eng = self._engine(
            _spec(name="floor", key="perf/h2d_overlap_frac",
                  objective=0.5, kind="lower")
        )
        t, fired = 0.0, False
        while t <= 3.0:
            if eng.evaluate(
                {"telemetry/perf/h2d_overlap_frac": 0.1}, now=t
            ):
                fired = True
                break
            t += 0.25
        assert fired

    def test_spec_validation(self):
        with pytest.raises(ValueError):
            _spec(name="Bad-Name")
        with pytest.raises(ValueError):
            _spec(kind="sideways")
        with pytest.raises(ValueError):
            _spec(budget=0.0)
        with pytest.raises(ValueError):
            _spec(fast_window_s=10.0, slow_window_s=1.0)
        with pytest.raises(ValueError):
            AlertEngine(
                [_spec(), _spec()], registry=Registry()
            )  # duplicate names

    def test_default_table_covers_run_surfaces(self):
        specs = default_slo_specs()
        keys = {s.key for s in specs}
        assert "serving/request_wait_ms_p99" in keys
        assert "pool/worker_step_ms_p99" in keys
        assert "perf/h2d_overlap_frac" in keys
        names = [s.name for s in specs]
        assert len(set(names)) == len(names)

    def test_format_status_line(self):
        eng = self._engine(_spec())
        assert eng.format_status() == "alerts firing: none"


class TestAlertSignal:
    def test_reads_engine_gauges(self):
        from torched_impala_tpu.control import AlertSignal

        reg = Registry()
        eng = AlertEngine(
            [_spec()], registry=reg, recorder=FlightRecorder(capacity=16)
        )
        t = 0.0
        while t <= 2.0:
            eng.evaluate({"telemetry/x/val_ms": 50.0}, now=t)
            t += 0.25
        snap = reg.snapshot()
        assert AlertSignal("probe").read(snap, t) == 1.0
        assert AlertSignal("probe", burn_rate=True).read(snap, t) > 1.0
        assert AlertSignal("unknown").read(snap, t) is None


# ---- merged trace export -------------------------------------------------


class TestMergedTrace:
    def _worker_dump(self, label, pid, lid):
        return {
            "label": label,
            "pid": pid,
            "trace": [
                [1_000_000, 500_000, "X", "pool/worker_step", 7, {"lid": lid}],
                [1_600_000, 0, "i", "pool/worker_ready", 7, {}],
            ],
            "thread_names": {"7": "worker"},
        }

    def test_per_process_rows_and_lineage(self):
        rec = FlightRecorder(capacity=64)
        rec.complete(
            "pool/submit_ack", 900_000, 900_000, {"lid": "a0u1"}
        )
        events = merge_chrome_events(
            rec,
            [
                self._worker_dump("proc0w0", 4242, "a0u1"),
                self._worker_dump("proc0w1", 4243, "a0u1"),
            ],
        )
        rows = {
            e["args"]["name"]: e["pid"]
            for e in events
            if e["ph"] == "M" and e["name"] == "process_name"
        }
        assert "proc0w0 (pid 4242)" in rows
        assert "proc0w1 (pid 4243)" in rows
        assert len({rows[r] for r in rows}) == len(rows)  # distinct rows
        worker_spans = [
            e for e in events if e["name"] == "pool/worker_step"
        ]
        assert len(worker_spans) == 2
        # Lineage IDs survive the merge: the worker span aligns under
        # the parent's submit->ack via args.lid.
        parent = next(e for e in events if e["name"] == "pool/submit_ack")
        assert all(
            e["args"]["lid"] == parent["args"]["lid"]
            for e in worker_spans
        )
        # Worker spans sit inside the parent span's time range.
        for e in worker_spans:
            assert parent["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"]
        names = [
            e["args"]["name"]
            for e in events
            if e["ph"] == "M" and e["name"] == "thread_name"
        ]
        assert names.count("worker") == 2

    def test_export_schema_validates(self, tmp_path):
        rec = FlightRecorder(capacity=16)
        rec.instant("learner/mark")
        agg = TelemetryAggregator()
        agg.retire("proc0w0", self._worker_dump("proc0w0", 1, "a0u0"))
        path = str(tmp_path / "merged.json")
        n = export_merged_trace(path, rec, agg)
        assert n == 3  # parent instant + worker X + worker i
        with open(path) as f:
            doc = json.load(f)
        validate_chrome_trace(doc)
        assert doc["displayTimeUnit"] == "ms"


# ---- dashboard -----------------------------------------------------------


class TestDash:
    def test_group_and_render(self, tmp_path):
        from tools.dash import fetch, group_metrics, render

        snap = {
            "telemetry/learner/steps": 10.0,
            "telemetry/proc0w0/pool/env_steps": 5.0,
            "telemetry/proc0w1/pool/env_steps": 6.0,
            "telemetry/alerts/firing_probe": 1.0,
            "telemetry/alerts/burn_rate_probe": 3.25,
        }
        path = str(tmp_path / "m.prom")
        write_metrics_file(path, to_openmetrics(snap))
        parsed = parse_openmetrics(fetch(path=path))
        groups, alerts = group_metrics(parsed)
        assert set(groups) == {"local", "proc0w0", "proc0w1"}
        assert groups["proc0w0"] == {"pool_env_steps": 5.0}
        assert alerts == {
            "firing_probe": 1.0,
            "burn_rate_probe": 3.25,
        }
        frame = render(parsed, color=False)
        assert "probe=FIRING" in frame
        assert "[proc0w1]" in frame
        assert "learner_steps" in frame


# ---- env-pool integration (crash paths) ----------------------------------


def _obs_scripted_factory(seed: int, env_index=None):
    from torched_impala_tpu.envs.fake import ScriptedEnv

    env = ScriptedEnv(episode_len=5)
    env.task_id = 0 if env_index is None else env_index
    return env


class TestPoolFanIn:
    def test_fanin_kill_repair_and_harvest(self):
        """One pool lifecycle, four ISSUE 17 acceptance points:
        (a) live fan-in — worker-prefixed series appear in the
        aggregated snapshot; (b) SIGKILL mid-run never corrupts the
        parent view and the repair leaves NO stale pid behind;
        (c) close() harvests every worker's final trace dump (with
        lineage IDs) into the aggregator; (d) the snapshot-lane
        segment is unlinked with the pool."""
        from multiprocessing import shared_memory

        from torched_impala_tpu.runtime.env_pool import ProcessEnvPool

        agg = TelemetryAggregator()
        pool = ProcessEnvPool(
            env_factory=_obs_scripted_factory,
            num_workers=2,
            envs_per_worker=2,
            obs_shape=(4,),
            obs_dtype=np.float32,
            base_seed=0,
            max_restarts=4,
            aggregator=agg,
        )
        lane_name = pool._snap_lane.descriptor()[0]
        try:
            assert agg.labels() == ["proc0w0", "proc0w1"]
            pool.trace_lineage = "a0u7"
            pool.reset_all()
            # (a) drive steps until both workers' snapshots fan in.
            deadline = time.monotonic() + 30
            snap = {}
            while time.monotonic() < deadline:
                pool.step_all(np.zeros(4, np.int32))
                snap = agg.aggregated_snapshot({})
                if (
                    snap.get("telemetry/proc0w0/pool/env_steps", 0) > 0
                    and snap.get("telemetry/proc0w1/pool/env_steps", 0)
                    > 0
                ):
                    break
                time.sleep(0.05)
            assert snap.get("telemetry/proc0w0/pool/env_steps", 0) > 0, snap
            assert "telemetry/proc0w0/pool/worker_step_ms_p50" in snap
            pids_before = agg.worker_pids()
            assert len(pids_before) == 2

            # (b) SIGKILL worker 0 mid-run; the pool repairs it and the
            # dead pid must vanish from the aggregate (no stale leak).
            os.kill(pool._procs[0].pid, signal.SIGKILL)
            pool._procs[0].join(timeout=10)
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and pool.restarts < 1:
                pool.step_all(np.zeros(4, np.int32))
            assert pool.restarts >= 1, "pool never repaired"
            deadline = time.monotonic() + 30
            pids_after = {}
            while time.monotonic() < deadline:
                pool.step_all(np.zeros(4, np.int32))
                pids_after = agg.worker_pids()
                if pids_after.get("proc0w0", pids_before["proc0w0"]) != (
                    pids_before["proc0w0"]
                ):
                    break
                time.sleep(0.05)
            assert pids_after["proc0w0"] != pids_before["proc0w0"]
            assert pids_before["proc0w0"] not in pids_after.values()
        finally:
            pool.close()
        # (c) close() retired each worker's exit dump: the merged-trace
        # input carries worker_step records with the submit lineage.
        dumps = agg.trace_dumps()
        assert dumps, "close() harvested no trace dumps"
        recs = [
            r
            for d in dumps
            for r in d["trace"]
            if r[3] == "pool/worker_step"
        ]
        assert recs
        assert any(r[5] == {"lid": "a0u7"} for r in recs), recs[:3]
        assert agg.labels() == []  # live sources detached at close
        # (d) the fan-in segment is gone with the pool.
        with pytest.raises(FileNotFoundError):
            shared_memory.SharedMemory(name=lane_name)
