"""Observability subsystem: tracer, metrics registry, merged timelines.

Covers the ISSUE-8 contracts: the disabled-path no-op fast path (<5%
on a hot pingpong loop), ring-buffer overwrite semantics, the one-reset
equivalence of the legacy stats entry points, and end-to-end traced
pRUN runs producing schema-valid Chrome-trace JSON with per-rank
tracks, monotone offset-aligned times, and (on hier) correct per-fabric
send attribution.
"""

from __future__ import annotations

import json
import time

import numpy as np
import pytest

from repro.comm import run_spmd
from repro.comm.collectives import coll_stats, reset_coll_stats
from repro.core.redist import exec_stats, reset_exec_stats
from repro.obs import metrics, report
from repro.obs import trace as tr


@pytest.fixture(autouse=True)
def _clean_trace_state():
    """Tests toggle the module-level flag; restore the disabled default
    (and with it, no runtime hooks installed)."""
    was = tr.enabled
    yield
    if was:
        tr.enable_trace()
    else:
        tr.disable_trace()
    tr.reset_trace()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_counter_gauge_histogram(self):
        c = metrics.counter("t.obs.c")
        c.reset()
        c.inc()
        c.inc(4)
        assert c.value == 5
        g = metrics.gauge("t.obs.g")
        g.set(2.5)
        assert g.value == 2.5
        h = metrics.histogram("t.obs.h")
        h.reset()
        for x in (1.0, 2.0, 3.0, 4.0):
            h.observe(x)
        assert h.count == 4
        assert h.summary()["mean"] == 2.5
        assert h.percentile(0) == 1.0
        assert h.percentile(100) == 4.0
        assert h.percentile(50) == 2.5

    def test_get_or_create_and_type_guard(self):
        assert metrics.counter("t.obs.same") is metrics.counter("t.obs.same")
        with pytest.raises(TypeError):
            metrics.gauge("t.obs.same")

    def test_snapshot_prefix_and_delta(self):
        c = metrics.counter("t.obs.d1")
        c.reset()
        c.inc(3)
        snap = metrics.snapshot(prefix="t.obs.")
        assert snap["t.obs.d1"] == 3
        c.inc(2)
        d = metrics.delta(snap, prefix="t.obs.")
        assert d["t.obs.d1"] == 2

    def test_histogram_reservoir_bounded(self):
        h = metrics.Histogram("t.obs.bounded", max_samples=8)
        for i in range(100):
            h.observe(float(i))
        assert h.count == 100
        assert len(h.samples()) <= 8
        assert h.max == 99.0 and h.min == 0.0

    def test_reset_runs_weak_hooks(self):
        calls = []

        class Owner:
            def cb(self):
                calls.append(1)

        o = Owner()
        metrics.on_reset(o.cb)
        metrics.reset()
        assert calls == [1]
        del o
        metrics.reset()  # dead weakref: hook pruned, no error
        assert calls == [1]


class TestResetEquivalence:
    """ISSUE-8 satellite: the three legacy reset entry points must not
    drift — each is a thin alias of one registry-wide reset."""

    def test_reset_exec_stats_also_zeroes_coll_stats(self):
        metrics.counter("redist.messages").inc(7)
        metrics.counter("coll.ring_hops_into").inc(3)
        assert exec_stats()["messages"] == 7
        assert coll_stats()["ring_hops_into"] == 3
        reset_exec_stats()
        assert exec_stats()["messages"] == 0
        assert coll_stats()["ring_hops_into"] == 0

    def test_reset_coll_stats_also_zeroes_exec_stats(self):
        metrics.counter("redist.bytes").inc(11)
        reset_coll_stats()
        assert exec_stats()["bytes"] == 0

    def test_stats_dicts_are_registry_views(self):
        reset_exec_stats()
        metrics.counter("redist.copies").inc(2)
        assert exec_stats()["copies"] == 2
        assert metrics.snapshot(prefix="redist.")["redist.copies"] == 2


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


class TestTracer:
    def test_disabled_span_is_shared_noop(self):
        tr.disable_trace()
        s1 = tr.span("x", peer=1)
        s2 = tr.span("y")
        assert s1 is s2 is tr._NOOP
        with s1 as s:
            assert s.set(a=1) is s  # chainable, records nothing
        tr.instant("z")  # no-op, no error

    def test_span_and_instant_record(self):
        tr.enable_trace(capacity=64)
        tr.reset_trace()
        with tr.span("op.a", peer=3) as s:
            s.set(bytes=10)
        tr.instant("mark", k="v")
        evs = tr.events()
        assert [e[0] for e in evs] == ["op.a", "mark"]
        name, ph, ts, dur, attrs = evs[0]
        assert ph == "X" and dur >= 0 and attrs == {"peer": 3, "bytes": 10}
        assert evs[1][1] == "i"

    def test_ring_buffer_overwrites_oldest(self):
        tr.enable_trace(capacity=16)
        tr.reset_trace()
        for i in range(40):
            tr.instant("e", i=i)
        evs = tr.events()
        assert len(evs) == 16
        assert tr.dropped() == 24
        assert [e[4]["i"] for e in evs] == list(range(24, 40))

    def test_disabled_overhead_under_5pct_on_pingpong_hot_loop(self):
        """The traced call-site pattern with PPYTHON_TRACE=0 must cost
        one attribute check: <5% over the bare loop on a ThreadComm
        pingpong (interleaved best-of-N to shrug off scheduler noise)."""
        from repro.comm import get_context

        tr.disable_trace()
        iters = 500
        payload = np.arange(1024.0)

        def pingpong(traced):
            ctx = get_context()
            if ctx.pid == 0:
                t0 = time.perf_counter()
                if traced:
                    for i in range(iters):
                        with tr.span("send", peer=1, bytes=payload.nbytes):
                            ctx.send(1, ("t", i), payload)
                        with tr.span("recv", peer=1):
                            ctx.recv(1, ("t", i))
                else:
                    for i in range(iters):
                        ctx.send(1, ("t", i), payload)
                        ctx.recv(1, ("t", i))
                return time.perf_counter() - t0
            for i in range(iters):
                ctx.send(0, ("t", i), ctx.recv(0, ("t", i)))
            return 0.0

        # the traced call sites, disabled, must record nothing...
        tr.reset_trace()
        run_spmd(pingpong, 2, args=(True,))
        assert tr.events() == []

        # ...and must cost <5% of one pingpong iteration.  Differencing
        # two 2-thread wall-time runs drowns a ~2% effect in scheduler
        # noise, so bound the added cost analytically instead: the span
        # overhead is measured tightly in-process (best of 5 batches)
        # and compared against the best-of-3 untraced iteration time.
        per_iter = min(
            max(run_spmd(pingpong, 2, args=(False,))) for _ in range(3)
        ) / iters
        n = 20000
        span_cost = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _i in range(n):
                with tr.span("send", peer=1, bytes=payload.nbytes):
                    pass
                with tr.span("recv", peer=1):
                    pass
            span_cost = min(span_cost, (time.perf_counter() - t0) / n)
        assert span_cost <= per_iter * 0.05, (
            f"disabled spans add {span_cost * 1e9:.0f}ns per iteration = "
            f"{span_cost / per_iter:.1%} of a {per_iter * 1e6:.1f}us "
            f"pingpong iteration (contract: <5%)"
        )

    def test_complete_records_an_ended_span(self):
        tr.enable_trace(capacity=64)
        tr.reset_trace()
        tr.complete("op.done", 1.5, 0.25, rid=4)
        assert tr.events() == [("op.done", "X", 1.5, 0.25, {"rid": 4})]
        tr.disable_trace()
        tr.complete("op.done", 2.0, 0.1)
        assert len(tr.events()) == 1

    def test_instrument_context_noop_when_disabled(self):
        tr.disable_trace()

        class Dummy:
            def send(self):
                pass

            def recv(self):
                pass

        d = Dummy()
        assert tr.instrument_context(d) is d
        # no wrappers installed: the instance dict stays empty, so calls
        # hit the exact original bound methods
        assert "send" not in vars(d) and "recv" not in vars(d)
        assert not getattr(d, "_obs_instrumented", False)


# ---------------------------------------------------------------------------
# serve-engine and runtime spans
# ---------------------------------------------------------------------------


def _within(inner, outer) -> bool:
    """Event ``inner`` lies inside some event of ``outer`` in time."""
    _, _, ts, dur, _ = inner
    return any(o[2] <= ts and ts + dur <= o[2] + o[3] for o in outer)


def _jax_listeners():
    from jax._src import monitoring

    return (monitoring.get_event_duration_listeners()
            + monitoring.get_event_listeners())


class TestEngineAndRuntimeSpans:
    @pytest.fixture(scope="class")
    def engine(self):
        import jax
        import jax.numpy as jnp

        from repro.configs import get_config
        from repro.models import init_params
        from repro.serve import ContinuousBatchingEngine

        cfg = get_config("minicpm-2b").reduced()
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        eng = ContinuousBatchingEngine(cfg, params, slots=2, max_seq=32,
                                       prefill_pad=8, state_dtype=jnp.float32)
        eng.submit([1, 2], max_new=2)
        eng.run()  # compiled before any test records
        return eng

    def _serve(self, eng):
        reqs = [eng.submit([3, 4, 5], max_new=4), eng.submit([6], max_new=3)]
        eng.run()
        return reqs

    def test_engine_spans_nest_at_each_layer_boundary(self, engine):
        tr.enable_trace(capacity=4096)
        tr.reset_trace()
        self._serve(engine)
        by = {}
        for e in tr.events():
            by.setdefault(e[0], []).append(e)
        steps = by["serve.step"]
        assert len(steps) == len(by["serve.plan"]) >= 3
        assert all(_within(e, steps) for e in by["serve.plan"])
        assert all(_within(e, by["serve.prefill"]) for e in by["serve.prefill.wait"])
        assert all(_within(e, by["serve.decode"]) for e in by["serve.decode.wait"])
        assert len(by["serve.prefill.wait"]) == len(by["serve.prefill"]) >= 1
        assert len(by["serve.decode.wait"]) == len(by["serve.decode"]) >= 3
        assert all(_within(e, steps)
                   for n in ("serve.prefill", "serve.decode") for e in by[n])
        assert {"rows", "queued"} <= set(by["serve.plan"][0][4])

    def test_queue_span_carries_its_request(self, engine):
        tr.enable_trace(capacity=4096)
        tr.reset_trace()
        reqs = self._serve(engine)
        evs = tr.events()
        queue = {e[4]["rid"]: e for e in evs if e[0] == "serve.queue"}
        assert set(queue) == {r.rid for r in reqs}
        for r in reqs:
            _, _, ts, dur, _ = queue[r.rid]
            assert ts == r.arrival_t and ts + dur == pytest.approx(r.admit_t)
        admitted = [rid for e in evs if e[0] == "serve.prefill"
                    for rid in e[4]["rids"]]
        assert sorted(admitted) == sorted(queue)

    def test_gc_pass_records_one_span(self):
        import gc

        tr.enable_trace(capacity=64)
        tr.reset_trace()
        gc.disable()
        try:
            gc.collect()
        finally:
            gc.enable()
        (ev,) = [e for e in tr.events() if e[0] == "py.gc"]
        assert ev[1] == "X" and ev[3] >= 0
        assert ev[4]["generation"] == 2 and ev[4]["collected"] >= 0

    def test_gc_pass_inside_record_does_not_deadlock(self):
        """A GC pass can start while ``record`` holds the ring's lock and
        record its own span from the same thread.  In a child with a
        timeout, so a regression fails instead of hanging the suite."""
        import subprocess
        import sys

        code = (
            "import gc\n"
            "from repro.obs import trace as tr\n"
            "tr.enable_trace(capacity=1 << 16)\n"
            "gc.set_threshold(1, 1, 1)\n"
            "junk = []\n"
            "for i in range(20000):\n"
            "    with tr.span('op', i=i):\n"
            "        junk.append([i])\n"
            "    if len(junk) > 100:\n"
            "        junk.clear()\n"
            "names = [e[0] for e in tr.events()]\n"
            "print(names.count('op'), names.count('py.gc') > 0)\n"
        )
        out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                             text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        assert out.stdout.split() == ["20000", "True"]

    def test_fresh_jit_records_a_compile_span(self):
        import jax

        def fresh_obs_probe(x):
            return x * 3 + 1

        tr.enable_trace(capacity=256)
        tr.reset_trace()
        t0 = time.perf_counter()
        jax.jit(fresh_obs_probe)(np.float32(2)).block_until_ready()
        comp = [e for e in tr.events() if e[0] == "jax.compile"
                and e[4]["fun_name"] == "jit(fresh_obs_probe)"]
        assert len(comp) == 1
        assert t0 <= comp[0][2] and comp[0][3] > 0

    def test_disabled_records_nothing_and_leaves_no_hooks(self, engine):
        import gc

        import jax

        tr.enable_trace(capacity=64)
        assert tr._on_gc in gc.callbacks
        assert tr._on_jax_duration in _jax_listeners()
        tr.disable_trace()
        tr.reset_trace()
        assert tr._on_gc not in gc.callbacks
        assert not {tr._on_jax_duration, tr._on_jax_event} & set(_jax_listeners())
        self._serve(engine)
        gc.collect()
        jax.jit(lambda x: x - 7)(np.float32(1)).block_until_ready()
        assert tr.events() == []

    def test_import_leaves_jax_out(self):
        import subprocess
        import sys

        code = ("import sys, repro.obs; repro.obs.enable_trace(); "
                "print('jax' in sys.modules)")
        out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                             text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "False"

    def test_span_shows_on_the_profilers_host_plane(self, tmp_path):
        import glob

        import jax

        tr.enable_trace(capacity=64)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tr.span("obs.host_plane_probe"):
                time.sleep(0.001)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
        data = jax.profiler.ProfileData.from_file(path)
        host = [e for plane in data.planes if plane.name == "/host:CPU"
                for line in plane.lines for e in line.events
                if e.name == "obs.host_plane_probe"]
        assert len(host) == 1 and host[0].duration_ns >= 1e6


# ---------------------------------------------------------------------------
# schema validator
# ---------------------------------------------------------------------------


class TestSchemaValidator:
    def test_valid_doc_passes(self):
        doc = {
            "traceEvents": [
                {"name": "a", "ph": "X", "ts": 0.0, "dur": 1.0,
                 "pid": 0, "tid": 0},
                {"name": "process_name", "ph": "M", "pid": 0,
                 "args": {"name": "rank 0"}},
            ],
            "displayTimeUnit": "ms",
        }
        assert report.validate(doc, report.default_schema()) == []

    def test_violations_reported(self):
        schema = report.default_schema()
        assert report.validate({}, schema)  # missing traceEvents
        bad_ph = {"traceEvents": [{"name": "a", "ph": "Q", "pid": 0}]}
        assert any("ph" in e for e in report.validate(bad_ph, schema))
        neg_ts = {"traceEvents": [
            {"name": "a", "ph": "X", "ts": -5.0, "pid": 0}
        ]}
        assert any("minimum" in e for e in report.validate(neg_ts, schema))


# ---------------------------------------------------------------------------
# end-to-end traced pRUN runs
# ---------------------------------------------------------------------------


def _load_trace(path):
    with open(path) as f:
        doc = json.load(f)
    errs = report.validate(doc, report.default_schema())
    assert errs == [], errs
    return doc


@pytest.mark.slow
class TestTracedPRun:
    def test_two_rank_trace_schema_and_tracks(self, tmp_path):
        from repro.launch import pRUN

        res = pRUN(
            "repro.obs._selftest:traced_ring", 2, transport="file",
            timeout=120.0, trace=True,
            env={"PPYTHON_TRACE_DIR": str(tmp_path)},
        )
        assert len(res) == 2
        out = tmp_path / "ppython_trace_file_np2.json"
        doc = _load_trace(out)
        evs = doc["traceEvents"]
        pids = {e["pid"] for e in evs}
        assert pids == {0, 1}  # one track per rank
        # monotone per-rank: recorded order is timestamp order
        for pid in pids:
            ts = [e["ts"] for e in evs if e["pid"] == pid and e["ph"] == "X"]
            assert ts == sorted(ts)
            assert all(t >= 0.0 for t in ts)
        # offset-aligned: the two rank windows overlap (the bodies run
        # concurrently, so disjoint windows mean a broken clock merge)
        spans = {
            pid: [e["ts"] for e in evs if e["pid"] == pid and e["ph"] == "X"]
            for pid in pids
        }
        assert max(min(v) for v in spans.values()) < min(
            max(v) for v in spans.values()
        )
        # both fabrics' p2p + collective + compute spans are present
        names = {e["name"] for e in evs}
        assert {"comm.send", "comm.recv", "compute.spin"} <= names
        assert any(n.startswith("coll.") for n in names)

    def test_hier_trace_fabric_attribution_and_report(self, tmp_path):
        """ISSUE-8 acceptance: 2 virtual nodes, shm vs tcp sends
        attributed to the correct fabric, report prints per-rank
        comm/compute fractions."""
        from repro.launch import pRUN

        pRUN(
            "repro.obs._selftest:traced_all_pairs", 4, transport="hier",
            nodes=2, timeout=180.0, trace=True,
            env={"PPYTHON_TRACE_DIR": str(tmp_path)},
        )
        doc = _load_trace(tmp_path / "ppython_trace_hier_np4.json")
        sends = [e for e in doc["traceEvents"] if e["name"] == "comm.send"]
        assert sends, "no send spans recorded"
        checked = 0
        for e in sends:
            pid, args = e["pid"], e["args"]
            peer = args["peer"]
            same_node = (pid < 2) == (peer < 2)  # contiguous nodes=2
            assert args["fabric"] == ("shm" if same_node else "tcp"), (
                f"rank {pid} -> {peer} attributed to {args['fabric']}"
            )
            assert args["bytes"] > 0
            checked += 1
        assert checked >= 4  # both fabrics exercised in both directions
        s = report.summarize(doc)
        assert set(s["ranks"]) == {0, 1, 2, 3}
        for r in s["ranks"].values():
            assert 0.0 <= r["comm_frac"] <= 1.0
            assert abs(r["comm_frac"] + r["compute_frac"] - 1.0) < 1e-9

    def test_untraced_run_records_nothing(self, tmp_path):
        from repro.launch import pRUN

        pRUN(
            "repro.obs._selftest:traced_ring", 2, transport="file",
            timeout=120.0, trace=False,
            env={"PPYTHON_TRACE_DIR": str(tmp_path)},
        )
        assert list(tmp_path.glob("*.json")) == []


class TestMergeSingleRank:
    def test_local_merge_writes_single_track(self, tmp_path):
        from repro.comm.context import LocalComm

        tr.enable_trace(capacity=128)
        tr.reset_trace()
        with tr.span("solo.work"):
            pass
        out = tr.merge_traces(LocalComm(), path=tmp_path / "solo.json")
        doc = _load_trace(out)
        assert {e["pid"] for e in doc["traceEvents"]} == {0}
        assert any(e["name"] == "solo.work" for e in doc["traceEvents"])
