"""Observability layer tests (mpi_tpu/observe/ — ISSUE 8).

Covers the acceptance surface:

  * multi-rank trace merge produces ONE well-formed chrome trace with
    every rank's spans on its own track, clock-aligned;
  * the clock-offset estimate is sane on localhost (|offset| bounded
    by the measured RTT scale);
  * a chaos-killed rank under real ``mpirun`` leaves a flight-recorder
    postmortem naming its in-flight operation, and the launcher folds
    the dumps into one job report;
  * the ``--mpi-metrics-out`` JSON artifact round-trips its schema;
  * straggler detection records per-collective arrival skew;
  * with tracing disabled the per-op hooks stay in the noise (the
    <5% bounce budget is enforced by bench against the base commit;
    tier-1 asserts the absolute per-op hook cost is microseconds).
"""

import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import mpi_tpu
from mpi_tpu import collectives_generic as G
from mpi_tpu.observe import collect, flight, metrics
from mpi_tpu.observe import stream as spool
from mpi_tpu.utils import trace

from conftest import _free_port_block, run_on_ranks, tcp_cluster

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _clean_observe():
    import mpi_tpu.observe as observe

    observe.reset_for_testing()
    trace.clear()
    was = trace.enabled()
    yield
    observe.reset_for_testing()
    trace.clear()
    (trace.enable if was else trace.disable)()


# ---------------------------------------------------------------------------
# Distributed trace collection + clock alignment
# ---------------------------------------------------------------------------


class TestTraceCollection:
    def test_multirank_merge_well_formed(self, tmp_path):
        """4 in-process TCP ranks with tracing on: the merge yields one
        chrome-trace JSON with >= 4 rank tracks and clock-aligned
        send/receive span pairs."""
        out = tmp_path / "merged.json"
        trace.enable()
        with tcp_cluster(4) as nets:
            def fn(net, r):
                n = net.size()
                for step in range(3):
                    mpi_tpu.api.exchange(net, np.arange(8) + r,
                                         (r + 1) % n, (r - 1) % n, step)
                G.barrier(net)
                return collect.collect_and_merge(net, str(out))

            res = run_on_ranks(nets, fn, timeout=60)
        assert res[0] == str(out) and res[1] is None
        doc = json.loads(out.read_text())
        events = doc["traceEvents"]
        pids = {e["pid"] for e in events if e["ph"] == "X"}
        assert pids == {0, 1, 2, 3}
        # Process-name metadata per rank track.
        names = {e["pid"]: e["args"]["name"] for e in events
                 if e.get("name") == "process_name"}
        assert set(names) == {0, 1, 2, 3}
        assert "rank 2" in names[2]
        # Wire spans exist for every rank, with positive durations on a
        # shared (rebased, non-negative) timeline.
        for r in range(4):
            mine = [e for e in events if e["ph"] == "X" and e["pid"] == r]
            assert any(e["name"] == "wire.write" for e in mine)
            assert any(e["name"] == "wire.payload_wait" for e in mine)
            assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in mine)
        assert doc["metadata"]["missing_ranks"] == []

    def test_clock_offsets_sane_on_localhost(self, tmp_path):
        """In-process ranks share one physical clock: the estimated
        |offset| must be bounded (well under a second — it is RTT-scale
        scheduling noise, not a real clock difference)."""
        out = tmp_path / "merged.json"
        trace.enable()
        with tcp_cluster(3) as nets:
            run_on_ranks(
                nets, lambda net, r: collect.collect_and_merge(
                    net, str(out)), timeout=60)
        doc = json.loads(out.read_text())
        offs = doc["metadata"]["clock_offsets_us"]
        assert set(offs) == {"0", "1", "2"}
        assert offs["0"] == 0.0
        for r, off in offs.items():
            assert abs(off) < 0.5e6, (r, off)
            rtt = doc["metadata"]["clock_rtt_us"][r]
            assert 0 <= rtt < 0.5e6

    def test_offset_estimator_math(self):
        # Symmetric path: peer clock 1000 ns ahead, RTT 200 ns.
        est = collect.estimate_offsets([
            {"t0_ns": 0, "t1_ns": 200, "peer_ns": 1100},
            {"t0_ns": 0, "t1_ns": 1000, "peer_ns": 2000},  # worse RTT
        ])
        assert est["rtt_ns"] == 200
        assert est["offset_ns"] == 1000.0

    def test_shared_process_tracer_writes_one_copy(self, tmp_path):
        """In-process drivers (xla/hybrid rank threads share ONE tracer
        buffer) must not gather N duplicate copies of every span: rank
        0 writes the shared buffer once, flagged in metadata."""
        from mpi_tpu.backends.xla import run_spmd

        out = tmp_path / "xla.json"
        trace.enable()

        def main():
            mpi_tpu.init()
            mpi_tpu.barrier()
            # The shared buffer is written by rank 0 WITHOUT a rank
            # barrier (other ranks' finalize order is unconstrained) —
            # give sibling threads' span context managers a beat to
            # close so the snapshot deterministically holds all 4.
            time.sleep(0.3)
            from mpi_tpu.api import registered

            path = collect.collect_and_merge(registered(), str(out))
            mpi_tpu.finalize()
            return path

        res = run_spmd(main, n=4)
        assert sum(p is not None for p in res) == 1
        doc = json.loads(out.read_text())
        assert doc["metadata"]["shared_process_tracer"] is True
        assert doc["metadata"]["ranks"] == [0, 1, 2, 3]
        barriers = [e for e in doc["traceEvents"]
                    if e.get("name") == "mpi.barrier"]
        # One span per rank THREAD (tid lane), not 4 ranks x 4 copies.
        assert len(barriers) == 4
        assert len({e["tid"] for e in barriers}) == 4

    def test_single_rank_merge(self, tmp_path):
        out = tmp_path / "solo.json"
        trace.enable()
        with trace.span("solo.work"):
            pass
        with tcp_cluster(1) as nets:
            assert collect.collect_and_merge(nets[0], str(out)) == str(out)
        doc = json.loads(out.read_text())
        assert any(e.get("name") == "solo.work"
                   for e in doc["traceEvents"])


# ---------------------------------------------------------------------------
# Straggler detection
# ---------------------------------------------------------------------------


class TestStragglers:
    def test_cross_process_skew_from_aligned_entries(self):
        bundles = {
            0: {"pid": 1, "anchor_ns": 0, "events": [], "counters": {},
                "dropped": 0,
                "collective_entries": [("allreduce", 0, 1_000_000)]},
            1: {"pid": 2, "anchor_ns": 0, "events": [], "counters": {},
                "dropped": 0,
                "collective_entries": [("allreduce", 0, 5_000_000)]},
        }
        offsets = {0: {"offset_ns": 0.0, "rtt_ns": 0.0},
                   1: {"offset_ns": 1_000_000.0, "rtt_ns": 0.0}}
        doc = collect.merge_bundles(bundles, offsets)
        rows = doc["metadata"]["stragglers"]
        assert rows and rows[0]["collective"] == "allreduce"
        # rank 1 aligned arrival = 5ms - 1ms = 4ms → skew 3ms.
        assert rows[0]["skew_us"] == pytest.approx(3000.0)
        assert rows[0]["slowest_rank"] == 1

    def test_session_skew_recorded_for_xla_collectives(self):
        from mpi_tpu.backends.xla import run_spmd

        def main():
            mpi_tpu.init()
            if mpi_tpu.rank() == 2:
                time.sleep(0.05)  # deliberate straggler
            mpi_tpu.barrier()
            mpi_tpu.finalize()

        run_spmd(main, n=4)
        skews = metrics.session_skews()
        assert any(name == "barrier" and skew > 10_000 and slowest == 2
                   for name, skew, slowest in skews), skews


# ---------------------------------------------------------------------------
# Metrics artifact + summary
# ---------------------------------------------------------------------------


class TestMetricsArtifact:
    def test_schema_roundtrip(self, tmp_path):
        flight.configure(on=True)

        class Loop:
            """Facade-driven loopback: send parks the payload, receive
            takes it — enough to exercise the op-recording path."""

            def __init__(self):
                import queue

                self.q = queue.Queue()

            def init(self): pass
            def finalize(self): pass
            def rank(self): return 0
            def size(self): return 2
            def send(self, data, dest, tag): self.q.put(data)
            def receive(self, source, tag, out=None):
                return self.q.get(timeout=5)

        mpi_tpu.register(Loop())
        try:
            mpi_tpu.init()
            mpi_tpu.send(b"ping", 1, 5)
            assert mpi_tpu.receive(1, 5) == b"ping"
        finally:
            mpi_tpu.api._reset_for_testing()
        path = metrics.write(str(tmp_path / "m-{rank}.json"), rank=0,
                             size=2)
        assert path.endswith("m-0.json")
        doc = json.loads(Path(path).read_text())
        metrics.validate(doc)  # schema contract
        assert doc["rank"] == 0 and doc["schema_version"] == 1
        assert doc["ops"]["send"]["count"] >= 1
        assert doc["ops"]["send"]["p99_us"] >= doc["ops"]["send"]["p50_us"]
        # Round-trip: serialize → parse → validate again, unchanged.
        again = json.loads(json.dumps(doc))
        metrics.validate(again)
        assert again == doc

    def test_snapshot_holds_and_validate_accepts_compiles(self):
        import jax
        import jax.numpy as jnp

        assert trace.listen_compiles()

        @jax.jit
        def observed_program(x):
            return x + 1

        observed_program(jnp.ones(2))
        observed_program(jnp.ones(3))
        doc = json.loads(json.dumps(metrics.snapshot(rank=0, size=1)))
        metrics.validate(doc)
        row = doc["compiles"]["observed_program"]
        assert (row["traces"], row["lowerings"], row["compiles"]) == (2, 2, 2)
        assert row["compile_s"] > 0 and row["cache_hits"] == 0
        assert doc["compiles_dropped"] == trace.compiles_dropped()
        # `observe top` prints the costliest few of the process: one row.
        line, = metrics.compile_lines({"compiles": {"observed_program": row}})
        assert line.startswith("  compiles: observed_program x2 ")
        assert "(0 from cache; traced x2, lowered x2)" in line
        assert "  compiles: " in metrics.summary_text(rank=0)
        # An artifact from before the section validates; a broken one not.
        del doc["compiles"]
        metrics.validate(doc)
        for bad in ([], {"f": {"traces": 1}}, {"f": 3}):
            with pytest.raises(ValueError):
                metrics.validate(dict(doc, compiles=bad))

    def test_validate_rejects_malformed(self):
        with pytest.raises(ValueError):
            metrics.validate({"schema_version": 999})
        with pytest.raises(ValueError):
            metrics.validate({"schema_version": 1, "ops": [], "peers": {},
                              "counters": {}, "stragglers": [],
                              "elapsed_s": 1.0})

    def test_summary_text_renders(self):
        flight.configure(on=True)
        tok = flight.begin("send", 1, 7, 128)
        flight.end(tok)
        metrics.note_session_skew("allreduce", 123.0, 3)
        text = metrics.summary_text(rank=0)
        assert "observe top" in text
        assert "send" in text
        assert "slowest rank 3" in text

    def test_cli_top_renders_artifact(self, tmp_path):
        flight.configure(on=True)
        tok = flight.begin("send", 1, 7, 128)
        flight.end(tok)
        path = metrics.write(str(tmp_path / "m.json"), rank=0, size=1)
        res = subprocess.run(
            [sys.executable, "-m", "mpi_tpu.observe", "top", path],
            cwd=REPO, capture_output=True, text=True, timeout=60)
        assert res.returncode == 0, res.stderr
        assert "send" in res.stdout


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_is_bounded_and_names_inflight(self, tmp_path):
        flight.configure(on=True, cap=16)
        for i in range(40):
            tok = flight.begin("send", 1, i, 8)
            flight.end(tok)
        hung = flight.begin("receive", 2, 99)
        snap = flight.snapshot("test")
        assert len(snap["recent"]) == 16
        assert snap["op_counts"]["send"] == 40
        assert [e for e in snap["in_flight"]
                if e["op"] == "receive" and e["peer"] == 2
                and e["tag"] == 99]
        flight.end(hung, "error:Test")

    def test_dump_writes_postmortem(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MPI_TPU_POSTMORTEM_DIR", str(tmp_path))
        import mpi_tpu.observe as observe

        observe.reset_for_testing()
        flight.configure(on=True)
        flight.set_rank(3)
        flight.begin("send", 0, 11, 64)
        path = flight.dump("DeadlineError: test")
        assert path and os.path.exists(path)
        doc = json.loads(Path(path).read_text())
        assert doc["rank"] == 3 and doc["reason"].startswith("Deadline")
        assert doc["in_flight"][0]["op"] == "send"
        # First dump wins; cascade failures don't re-dump.
        assert flight.dump("PeerDeadError: cascade") is None

    def test_fatal_error_hook_dumps_on_typed_errors(self, tmp_path,
                                                    monkeypatch):
        monkeypatch.setenv("MPI_TPU_POSTMORTEM_DIR", str(tmp_path))
        import mpi_tpu.observe as observe
        from mpi_tpu.backends.rendezvous import DeadlineError

        observe.reset_for_testing()
        observe.fatal_error_hook(mpi_tpu.MpiError("benign"))
        assert not list(tmp_path.glob("postmortem-*.json"))
        observe.fatal_error_hook(DeadlineError("receive", 1.0))
        assert list(tmp_path.glob("postmortem-*.json"))


# ---------------------------------------------------------------------------
# End-to-end under real mpirun (integration)
# ---------------------------------------------------------------------------


def _run_mpirun(args, timeout=120, env=None):
    child_env = dict(os.environ)
    if env:
        child_env.update(env)
    return subprocess.run(
        [sys.executable, "-m", "mpi_tpu.launch.mpirun", *args],
        cwd=REPO, capture_output=True, text=True, timeout=timeout,
        env=child_env)


@pytest.mark.integration
class TestJobObservability:
    def test_mpirun_trace_out_merges_four_ranks(self, tmp_path):
        """The headline acceptance: a 4-rank mpirun job with tracing on
        emits ONE merged Perfetto JSON with >= 4 rank tracks and
        clock-aligned send/receive pairs."""
        prog = tmp_path / "traffic.py"
        prog.write_text(
            "import sys\n"
            "sys.path.insert(0, %r)\n"
            "import numpy as np\n"
            "import mpi_tpu\n"
            "mpi_tpu.init()\n"
            "r, n = mpi_tpu.rank(), mpi_tpu.size()\n"
            "for step in range(3):\n"
            "    mpi_tpu.sendrecv(np.arange(64) + r, dest=(r + 1) %% n,\n"
            "                     source=(r - 1) %% n, tag=step)\n"
            "mpi_tpu.barrier()\n"
            "mpi_tpu.finalize()\n" % str(REPO))
        out = tmp_path / "merged.json"
        port = _free_port_block(4)
        res = _run_mpirun(["--port-base", str(port), "--timeout", "30",
                           "--trace-out", str(out), "4", str(prog)],
                          env={"MPI_TPU_TRACE": "1"})
        assert res.returncode == 0, (res.stdout, res.stderr)
        doc = json.loads(out.read_text())
        events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["pid"] for e in events} == {0, 1, 2, 3}
        # Clock-aligned send/receive pairing (rendezvous semantics in
        # merged time): for a user-tag message, the receiver's
        # wire.payload_wait must sit inside the sender's
        # [write start, ack-wait end] window — the payload cannot have
        # been waited out before the sender wrote it, and the sender's
        # ack wait cannot end before the receiver matched the payload.
        # 10 ms slack absorbs the localhost clock-offset estimate.
        slack = 10_000.0
        user = [e for e in events
                if e.get("args", {}).get("tag", 1 << 60) < 3]
        writes = [e for e in user if e["name"] == "wire.write"]
        ackwaits = {(e["args"]["dest"], e["args"]["tag"]): e
                    for e in user if e["name"] == "wire.ack_wait"}
        waits = [e for e in user if e["name"] == "wire.payload_wait"]
        assert writes and waits and ackwaits
        checked = 0
        for w in writes:
            dest, tag = w["args"]["dest"], w["args"]["tag"]
            ack = ackwaits.get((dest, tag))
            if ack is None or ack["pid"] != w["pid"]:
                continue
            match = [p for p in waits
                     if p["pid"] == dest and p["args"]["tag"] == tag
                     and p["args"]["source"] == w["pid"]]
            assert match, (w, waits[:4])
            assert any(
                p["ts"] + p["dur"] >= w["ts"] - slack
                and p["ts"] + p["dur"] <= ack["ts"] + ack["dur"] + slack
                for p in match), (w, ack, match)
            checked += 1
        assert checked >= 4
        for r in ("0", "1", "2", "3"):
            assert abs(doc["metadata"]["clock_offsets_us"][r]) < 0.5e6

    def test_chaos_crash_yields_job_postmortem(self, tmp_path):
        """Acceptance: killing one rank under --mpi-chaos yields a
        collected job postmortem naming the dead rank's last in-flight
        operation."""
        prog = tmp_path / "crasher.py"
        prog.write_text(
            "import os, sys\n"
            "sys.path.insert(0, %r)\n"
            "os.environ['MPI_TPU_CHAOS'] = '3:1:crash@4'\n"
            "import mpi_tpu\n"
            "mpi_tpu.init()\n"
            "r, n = mpi_tpu.rank(), mpi_tpu.size()\n"
            "for step in range(100):\n"
            "    mpi_tpu.sendrecv(r, dest=(r + 1) %% n,\n"
            "                     source=(r - 1) %% n, tag=step)\n"
            "sys.exit(0)\n" % str(REPO))
        pm = tmp_path / "pm"
        port = _free_port_block(2)
        res = _run_mpirun(["--port-base", str(port), "--timeout", "30",
                           "--postmortem-dir", str(pm), "2", str(prog)])
        assert res.returncode != 0
        report = pm / "job_postmortem.json"
        assert report.exists(), res.stderr
        doc = json.loads(report.read_text())
        # The chaos-killed rank dumped on its way down, naming the op
        # it was inside when the injected death fired.
        crashed = [snap for snap in doc["ranks"].values()
                   if "chaos crash@4" in snap.get("reason", "")]
        assert crashed, doc["ranks"].keys()
        assert crashed[0]["in_flight"], "dead rank's in-flight op missing"
        assert crashed[0]["in_flight"][0]["op"] in (
            "send", "receive", "sendrecv")
        assert "last in-flight op" in res.stderr

    def test_metrics_out_artifacts_per_rank(self, tmp_path):
        prog = tmp_path / "pingpong.py"
        prog.write_text(
            "import sys\n"
            "sys.path.insert(0, %r)\n"
            "import mpi_tpu\n"
            "mpi_tpu.init()\n"
            "r = mpi_tpu.rank()\n"
            "for i in range(5):\n"
            "    if r == 0:\n"
            "        mpi_tpu.send(b'x' * 512, 1, i)\n"
            "    else:\n"
            "        mpi_tpu.receive(0, i)\n"
            "mpi_tpu.finalize()\n" % str(REPO))
        pattern = tmp_path / "metrics-{rank}.json"
        port = _free_port_block(2)
        res = _run_mpirun(["--port-base", str(port), "--timeout", "30",
                           "--metrics-out", str(pattern), "2", str(prog)])
        assert res.returncode == 0, res.stderr
        from mpi_tpu.observe import metrics as m

        for r, op in ((0, "send"), (1, "receive")):
            doc = json.loads((tmp_path / f"metrics-{r}.json").read_text())
            m.validate(doc)
            assert doc["rank"] == r
            assert doc["ops"][op]["count"] == 5


# ---------------------------------------------------------------------------
# Overhead smoke (tier-1): tracing disabled must stay in the noise
# ---------------------------------------------------------------------------


class TestDisabledOverhead:
    def test_disabled_paths_are_single_checks(self):
        """With tracing AND the flight recorder off, a facade op adds
        only flag checks — no recorder or tracer mutation."""
        flight.configure(on=False)
        trace.disable()
        calls = []

        class Probe:
            def init(self): pass
            def finalize(self): pass
            def rank(self): return 0
            def size(self): return 2
            def send(self, data, dest, tag): calls.append("send")
            def receive(self, source, tag, out=None): return b""

        mpi_tpu.register(Probe())
        try:
            mpi_tpu.init()
            before = flight.snapshot()["op_counts"].get("send", 0)
            mpi_tpu.send(b"x", 1, 0)
            assert calls == ["send"]
            assert flight.snapshot()["op_counts"].get("send", 0) == before
            assert trace.events() == []
        finally:
            mpi_tpu.api._reset_for_testing()

    def test_per_op_hook_cost_is_microseconds(self):
        """The absolute cost of one begin/end pair (the only work the
        recorder adds to an op) must be microseconds — <5% of even the
        fastest real transport op. The bounce-level <5% regression gate
        runs in bench against the base commit; this is the tier-1
        smoke for the same budget."""
        flight.configure(on=True)
        n = 5000
        t0 = time.perf_counter()
        for i in range(n):
            flight.end(flight.begin("send", 1, i, 64))
        per_op_us = (time.perf_counter() - t0) / n * 1e6
        # Generous bound (CI boxes vary): tens of µs would mean a real
        # regression; the measured cost is ~1-3 µs.
        assert per_op_us < 25.0, per_op_us

    def test_span_disabled_is_one_bool_check(self):
        trace.disable()
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            with trace.span("x"):
                pass
        per_us = (time.perf_counter() - t0) / n * 1e6
        assert per_us < 10.0, per_us


# ---------------------------------------------------------------------------
# Streaming trace spooling (ISSUE 15 tentpole)
# ---------------------------------------------------------------------------


class TestStreamingSpool:
    def test_chunk_roundtrip_and_scan(self, tmp_path, monkeypatch):
        """Spooled chunks + footer read back into one bundle; scan_spools
        keys it by rank."""
        monkeypatch.setenv("MPI_TPU_TRACE_STREAM_EVENTS", "4")
        w = spool.SpoolWriter(str(tmp_path), rank=3)
        w.write_chunk([{"name": f"op{i}", "ts_us": float(i),
                        "dur_us": 1.0} for i in range(4)])
        w.write_chunk([{"name": "tail", "ts_us": 9.0, "dur_us": 1.0}])
        w.write_footer()
        w.close()
        assert w.chunks_written == 2 and w.events_written == 5
        b = spool.parse_spool(w.path)
        assert b is not None and b["rank"] == 3
        assert len(b["events"]) == 5 and b["spool_chunks"] == 2
        assert b["events"][0]["name"] == "op0"
        assert b["events"][-1]["name"] == "tail"
        found = spool.scan_spools(str(tmp_path))
        assert set(found) == {3}
        assert len(found[3]["events"]) == 5

    def test_torn_trailing_line_tolerated(self, tmp_path):
        """Death mid-write leaves a truncated last line; everything
        before it must still parse (the crash-durability contract)."""
        w = spool.SpoolWriter(str(tmp_path), rank=1)
        w.write_chunk([{"name": "a", "ts_us": 0.0, "dur_us": 1.0}])
        w.write_chunk([{"name": "b", "ts_us": 1.0, "dur_us": 1.0}])
        w.close()
        raw = Path(w.path).read_text()
        lines = raw.splitlines(keepends=True)
        Path(w.path).write_text(lines[0] + lines[1][: len(lines[1]) // 2])
        b = spool.parse_spool(w.path)
        assert b is not None
        assert [e["name"] for e in b["events"]] == ["a"]

    def test_tracer_streams_at_watermark(self, tmp_path, monkeypatch):
        """The tracer's resident buffer stays O(chunk): batches detach
        to the spool at the size watermark, and flush_stream pushes the
        sub-chunk tail."""
        monkeypatch.setenv("MPI_TPU_TRACE_STREAM_EVENTS", "4")
        trace.enable()
        w = spool.SpoolWriter(str(tmp_path), rank=0)
        trace.set_stream(w)
        for i in range(10):
            trace.add_span(f"s{i}", float(i), 1.0)
        assert w.chunks_written == 2          # 2 full chunks of 4
        assert len(trace.events()) == 2       # resident tail only
        assert trace.flush_stream() == 2
        assert trace.events() == []
        assert w.events_written == 10
        b = spool.parse_spool(w.path)
        assert len(b["events"]) == 10

    def test_age_watermark_flushes_stale_tail(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MPI_TPU_TRACE_STREAM_EVENTS", "1000")
        monkeypatch.setenv("MPI_TPU_TRACE_STREAM_AGE_S", "0.05")
        trace.enable()
        w = spool.SpoolWriter(str(tmp_path), rank=0)
        trace.set_stream(w)
        trace.add_span("old", 0.0, 1.0)
        assert w.chunks_written == 0
        time.sleep(0.1)
        trace.add_span("young", 1.0, 1.0)   # arrival check fires the age
        assert w.chunks_written == 1
        assert trace.events() == []

    def test_broken_writer_goes_silent(self, tmp_path):
        """Spool I/O failure must never take the job down: the writer
        records the error and becomes a no-op."""
        target = tmp_path / "not-a-dir"
        target.write_text("file, not a directory")
        w = spool.SpoolWriter(str(target), rank=0)
        w.write_chunk([{"name": "x", "ts_us": 0.0, "dur_us": 1.0}])
        assert w.broken is not None
        w.write_chunk([{"name": "y", "ts_us": 1.0, "dur_us": 1.0}])
        w.write_footer()  # still silent
        w.close()

    def test_streaming_overhead_smoke(self, tmp_path, monkeypatch):
        """Satellite: streaming on must stay within the same per-event
        budget as the plain tracer — the flush is amortized over the
        chunk, so the hot path adds an attribute check and a batch
        handoff every N events."""
        n = 4000
        trace.enable()
        t0 = time.perf_counter()
        for i in range(n):
            trace.add_span("plain", float(i), 1.0)
        plain_us = (time.perf_counter() - t0) / n * 1e6
        trace.clear()
        monkeypatch.setenv("MPI_TPU_TRACE_STREAM_EVENTS", "512")
        w = spool.SpoolWriter(str(tmp_path), rank=0)
        trace.set_stream(w)
        t0 = time.perf_counter()
        for i in range(n):
            trace.add_span("streamed", float(i), 1.0)
        streamed_us = (time.perf_counter() - t0) / n * 1e6
        assert w.chunks_written >= n // 512
        # Generous absolute bounds (CI boxes vary); the point is that
        # neither path costs tens of microseconds per span.
        assert plain_us < 50.0, plain_us
        assert streamed_us < 50.0, streamed_us

    def test_local_bundle_includes_spooled_events(self, tmp_path,
                                                  monkeypatch):
        """The Finalize gather must stay complete under streaming:
        already-flushed chunks are read back and prepended to the
        resident tail."""
        monkeypatch.setenv("MPI_TPU_TRACE_STREAM_EVENTS", "2")
        trace.enable()
        w = spool.SpoolWriter(str(tmp_path), rank=0)
        trace.set_stream(w)
        for i in range(5):
            trace.add_span(f"s{i}", float(i), 1.0)
        b = collect.local_bundle(0)
        assert [e["name"] for e in b["events"]] == [
            f"s{i}" for i in range(5)]
        assert b["spool"] == w.path and b["spool_chunks"] == 2

    def test_gather_recovers_missing_rank_from_spool(self, tmp_path,
                                                     monkeypatch):
        """Rank 0's gather reconstructs a dead rank's track from its
        spool file; the rank stays listed as missing (it IS dead) and
        is flagged as spool-reconstructed."""
        monkeypatch.setenv("MPI_TPU_TRACE_STREAM", str(tmp_path))
        import mpi_tpu.observe as observe

        observe.reset_for_testing()  # re-resolve config with the env
        dead = spool.SpoolWriter(str(tmp_path), rank=1)
        dead.write_chunk([{"name": "dead.work", "ts_us": 5.0,
                           "dur_us": 2.0}])
        dead.close()
        bundles = {0: collect.local_bundle(0)}
        offsets = {0: {"offset_ns": 0.0, "rtt_ns": 0.0}}
        missing = [1]
        recovered = collect._recover_from_spools(bundles, offsets, missing)
        assert recovered == [1]
        assert 1 in bundles and bundles[1]["events"][0]["name"] == \
            "dead.work"
        assert missing == [1]  # stays dead

    def test_footer_written_once(self, tmp_path):
        w = spool.SpoolWriter(str(tmp_path), rank=0)
        w.write_chunk([{"name": "x", "ts_us": 0.0, "dur_us": 1.0}])
        w.write_footer()
        w.write_footer()
        w.close()
        lines = Path(w.path).read_text().splitlines()
        assert sum(1 for ln in lines
                   if json.loads(ln)["t"] == "footer") == 1


# ---------------------------------------------------------------------------
# Native wirecore stage spans (ISSUE 15 tentpole)
# ---------------------------------------------------------------------------


class TestNativeStageSpans:
    def test_stage_child_spans_on_tcp_path(self):
        """Acceptance: with tracing on, the native TCP data path emits
        wire.write.assemble / wire.write.syscall / wire.recv.syscall
        child spans and the wire.native.* counters."""
        from mpi_tpu import native as native_mod

        if not native_mod.available("wirecore"):
            pytest.skip("native wirecore unavailable here")
        trace.enable()
        with tcp_cluster(2) as nets:
            def fn(net, r):
                if r == 0:
                    net.send(np.zeros(16384, np.float32), 1, 3)
                else:
                    net.receive(0, 3)

            run_on_ranks(nets, fn, timeout=30)
        evs = trace.events()
        names = {e["name"] for e in evs}
        assert "wire.write.assemble" in names
        assert "wire.write.syscall" in names
        assert "wire.recv.syscall" in names
        counters = trace.counters()
        assert counters.get("wire.native.tx.writev_calls", 0) >= 1
        assert counters.get("wire.native.rx.recv_calls", 0) >= 1
        assert counters.get("wire.native.tx.syscall_ns", 0) > 0
        # Child spans start no earlier than their wire.write parent and
        # the syscall child carries the byte count.
        writes = [e for e in evs if e["name"] == "wire.write"]
        for c in (e for e in evs if e["name"] == "wire.write.syscall"):
            assert any(w["ts_us"] <= c["ts_us"] + 1.0 for w in writes), c
            assert c["bytes"] > 0 and c["writev_calls"] >= 1


# ---------------------------------------------------------------------------
# Decode-phase deadline (ISSUE 15 satellite)
# ---------------------------------------------------------------------------


class TestDecodeDeadline:
    def test_slow_decode_trips_optimeout(self, monkeypatch):
        """--mpi-optimeout now covers the decode phase: a payload that
        arrives in time but decodes past the deadline raises
        DeadlineError instead of returning arbitrarily late."""
        from mpi_tpu.backends import tcp as tcpmod

        real = tcpmod.codec_decode

        def slow(payload, out=None):
            time.sleep(0.6)
            return real(payload, out=out)

        with tcp_cluster(2, optimeout=0.2) as nets:
            monkeypatch.setattr(tcpmod, "codec_decode", slow)

            def fn(net, r):
                if r == 0:
                    net.send(b"x" * 64, 1, 7)
                else:
                    with pytest.raises(tcpmod.DeadlineError) as ei:
                        net.receive(0, 7)
                    assert "decode" in str(ei.value)

            run_on_ranks(nets, fn, timeout=30)

    def test_fast_decode_unaffected(self):
        with tcp_cluster(2, optimeout=5.0) as nets:
            def fn(net, r):
                if r == 0:
                    net.send(b"y" * 64, 1, 8)
                else:
                    assert bytes(net.receive(0, 8)) == b"y" * 64

            run_on_ranks(nets, fn, timeout=30)


# ---------------------------------------------------------------------------
# Crash-durable spooling under real mpirun (integration)
# ---------------------------------------------------------------------------


@pytest.mark.integration
class TestCrashDurableSpooling:
    def test_sigkill_mid_bounce_reconstructs_trace(self, tmp_path):
        """Acceptance: a rank SIGKILLed mid-bounce (no atexit, no
        finalize, no flight dump) still appears in the merged trace with
        its last flushed spans, reconstructed from its spool file; its
        tail is folded into the job postmortem."""
        prog = tmp_path / "bounce_kill.py"
        prog.write_text(
            "import os, signal, sys\n"
            "sys.path.insert(0, %r)\n"
            "import mpi_tpu\n"
            "mpi_tpu.init()\n"
            "r = mpi_tpu.rank()\n"
            "for i in range(60):\n"
            "    if r == 0:\n"
            "        mpi_tpu.send(b'x' * 512, 1, i)\n"
            "        mpi_tpu.receive(1, 1000 + i)\n"
            "    else:\n"
            "        mpi_tpu.receive(0, i)\n"
            "        if i == 25:\n"
            "            os.kill(os.getpid(), signal.SIGKILL)\n"
            "        mpi_tpu.send(b'y' * 512, 0, 1000 + i)\n"
            "mpi_tpu.finalize()\n" % str(REPO))
        spools = tmp_path / "spools"
        out = tmp_path / "merged.json"
        port = _free_port_block(2)
        res = _run_mpirun(
            ["--port-base", str(port), "--timeout", "30",
             "--optimeout", "10", "--trace-stream", str(spools),
             "--trace-out", str(out), "2", str(prog)],
            env={"MPI_TPU_TRACE_STREAM_EVENTS": "8"})
        assert res.returncode != 0
        # Both ranks spooled; the dead rank's file survives its SIGKILL.
        assert list(spools.glob("spool-rank1-*.ndjson")), res.stderr
        # The launcher reconstructed the merged trace from spools alone
        # (the Finalize gather never ran — rank 0 died on peer loss).
        doc = json.loads(out.read_text())
        assert doc["metadata"]["source"] == "spool-reconstruction"
        dead = [e for e in doc["traceEvents"]
                if e.get("ph") == "X" and e["pid"] == 1]
        assert dead, "dead rank's spooled spans missing from the trace"
        names = {e["name"] for e in dead}
        assert any(n.startswith(("mpi.", "wire.")) for n in names), names
        # Spool tails folded into the job report, with the dead rank's
        # final moments echoed despite the absent flight dump.
        report = json.loads((spools / "job_postmortem.json").read_text())
        assert report["spool_tails"]["1"]["last_spans"]
        assert "no flight dump; last spooled span" in res.stderr

    def test_chaos_crash_spool_survives(self, tmp_path):
        """Chaos crash@K flushes the spool tail on its way down, so the
        reconstructed trace carries the rank's pre-crash spans."""
        prog = tmp_path / "chaos_bounce.py"
        prog.write_text(
            "import os, sys\n"
            "sys.path.insert(0, %r)\n"
            "os.environ['MPI_TPU_CHAOS'] = '3:1:crash@6'\n"
            "import mpi_tpu\n"
            "mpi_tpu.init()\n"
            "r, n = mpi_tpu.rank(), mpi_tpu.size()\n"
            "for step in range(100):\n"
            "    mpi_tpu.sendrecv(r, dest=(r + 1) %% n,\n"
            "                     source=(r - 1) %% n, tag=step)\n"
            "sys.exit(0)\n" % str(REPO))
        spools = tmp_path / "spools"
        out = tmp_path / "merged.json"
        pm = tmp_path / "pm"
        port = _free_port_block(2)
        res = _run_mpirun(
            ["--port-base", str(port), "--timeout", "30",
             "--postmortem-dir", str(pm), "--trace-stream", str(spools),
             "--trace-out", str(out), "2", str(prog)],
            env={"MPI_TPU_TRACE_STREAM_EVENTS": "8"})
        assert res.returncode != 0
        doc = json.loads(out.read_text())
        assert doc["metadata"]["source"] == "spool-reconstruction"
        crashed_pids = {e["pid"] for e in doc["traceEvents"]
                        if e.get("ph") == "X"}
        assert crashed_pids, "no spooled spans reconstructed"
        report = json.loads((pm / "job_postmortem.json").read_text())
        # Flight dumps (chaos crash runs them) AND spool tails coexist.
        assert report["ranks"]
        assert report["spool_tails"]
        for r in report["spool_tails"].values():
            assert r["events_spooled"] > 0


# ---------------------------------------------------------------------------
# Satellite regression tests (ADVICE.md round 5)
# ---------------------------------------------------------------------------


class TestSatellites:
    def test_reserve_tag_blocks_spans_large_worlds(self):
        """allreduce_compressed_wire's 4n tags must claim ceil(4n/4096)
        consecutive blocks so world sizes > 1024 can't spill into the
        next collective's block."""
        class Impl:
            pass

        impl = Impl()
        base1 = G.reserve_tag_blocks(impl, 4 * 2050)  # 8200 tags → 3 blocks
        base2 = G._next_tag_base(impl)
        assert base1 == G.COLL_TAG_BASE
        assert base2 - base1 == 3 * G._TAGS_PER_COLLECTIVE
        assert base2 > base1 + 4 * 2050 - 1  # no overlap with the span
        # Normal collectives still consume exactly one block.
        assert G._next_tag_base(impl) - base2 == G._TAGS_PER_COLLECTIVE

    def test_tagmanager_cancel_false_after_payload_arrived(self):
        """MPI contract: a successful cancel implies NO part of the
        message was received — a buffered payload defeats the cancel."""
        from mpi_tpu.backends.rendezvous import (ReceiveCancelled,
                                                 TagManager)

        tm = TagManager("receive", peer=1)
        slot, gen = tm.claim(7)
        tm.route(7, bytearray(b"payload"))
        exc = ReceiveCancelled("test")
        assert tm.cancel(7, exc) is False
        assert bytes(tm.wait(slot, gen)) == b"payload"
        tm.release(7)
        # Without a buffered payload the cancel still succeeds.
        slot, gen = tm.claim(8)
        assert tm.cancel(8, exc) is True
        with pytest.raises(ReceiveCancelled):
            tm.wait(slot, gen)
        tm.release(8)

    def test_create_struct_alignment_epsilon(self):
        """{double@0, char@8} pads its extent to 16 (the strictest
        component alignment), as MPICH/mpi4py do — not 9."""
        from mpi_tpu.compat import MPI

        st = MPI.Datatype.Create_struct(
            [1, 1], [0, 8], [MPI.DOUBLE, MPI.CHAR])
        assert st.Get_size() == 9        # data bytes only
        assert st.Get_extent() == (0, 16)  # aligned stride
        # Packed layouts keep the Create_resized escape hatch.
        packed = st.Create_resized(0, 9)
        assert packed.Get_extent() == (0, 9)
        # All-char structs stay byte-aligned (no spurious padding).
        st2 = MPI.Datatype.Create_struct([1, 1], [0, 1],
                                         [MPI.CHAR, MPI.CHAR])
        assert st2.Get_extent() == (0, 2)
