"""End-to-end causal tracing tests (telemetry/tracectx.py, ISSUE 8):
cross-thread trace parenting (producer / serving drain-thread spans attach
to the submitting trace), histogram exemplars + exposition-format escaping,
slow-trace ring eviction order, the /traces endpoint and `traces` CLI verb,
disabled-mode overhead (no contextvar churn on the step path beyond an
attribute read and a branch), and the serving p99-decomposition acceptance:
one connected submit->queue->drain->device->resolve trace whose child-span
durations decompose the recorded latency."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.telemetry import tracectx
from deeplearning4j_tpu.telemetry.tracectx import SlowTraceRing
from deeplearning4j_tpu.datasets.iterator import (ArrayDataSetIterator,
                                                  AsyncDataSetIterator,
                                                  DataSetIterator)
from deeplearning4j_tpu.nn import layers as L, updaters as U
from deeplearning4j_tpu.nn.conf import inputs as I
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


@pytest.fixture(autouse=True)
def _isolate():
    """Telemetry isolation (registry, tracer, slow-trace ring) around
    every test via the one-call telemetry.reset()."""
    telemetry.reset()
    telemetry.disable()
    yield
    telemetry.reset()
    telemetry.disable()


@pytest.fixture
def traced(_isolate):
    """Telemetry ON (the one toggle flips metrics, spans AND trace
    contexts); yields the enabled default registry."""
    telemetry.enable()
    yield telemetry.get_registry()


def _mlp(n_in=4, n_out=2, hidden=8, seed=0):
    net = MultiLayerNetwork(
        NeuralNetConfig(seed=seed, updater=U.Sgd(learning_rate=0.1)).list(
            L.DenseLayer(n_out=hidden, activation="tanh"),
            L.OutputLayer(n_out=n_out, loss="mcxent"),
            input_type=I.FeedForwardType(n_in)))
    net.init()
    return net


def _xy(n=32, n_in=4, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.rand(n, n_in).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, n)]
    return x, y


def _spans_by_name(doc):
    out = {}
    for s in doc["spans"]:
        out.setdefault(s["name"], []).append(s)
    return out


# ---------------------------------------------------------------------------
# core: contexts, parenting, lifecycle
# ---------------------------------------------------------------------------

class TestTraceContextCore:
    def test_maybe_start_is_none_when_disabled(self):
        assert tracectx.maybe_start("x") is None
        assert tracectx.current() is None
        assert tracectx.current_trace_id() is None
        with tracectx.attach(None):  # no-op block, no branching at sites
            assert tracectx.current() is None

    def test_same_thread_span_nesting_builds_parent_chain(self, traced):
        ctx = tracectx.start_trace("req", model="m")
        with tracectx.attach(ctx):
            with telemetry.span("outer"):
                with telemetry.span("inner"):
                    pass
        assert ctx.finish()
        doc = tracectx.get_ring().find(ctx.trace_id)
        by = _spans_by_name(doc)
        root, = by["req"]
        outer, = by["outer"]
        inner, = by["inner"]
        assert root["parent_id"] is None
        assert outer["parent_id"] == root["span_id"]
        assert inner["parent_id"] == outer["span_id"]
        # span ids are unique within the trace
        ids = [s["span_id"] for s in doc["spans"]]
        assert len(ids) == len(set(ids))

    def test_finish_is_idempotent_and_open_count_balances(self, traced):
        base = tracectx.open_trace_count()
        ctx = tracectx.start_trace("req")
        assert tracectx.open_trace_count() == base + 1
        assert ctx.finish()
        assert not ctx.finish()  # racing finishers: second is a no-op
        assert tracectx.open_trace_count() == base

    def test_abandoned_trace_never_rings(self, traced):
        ctx = tracectx.start_trace("req")
        assert ctx.abandon()
        assert tracectx.get_ring().find(ctx.trace_id) is None
        assert tracectx.open_trace_count() == 0

    def test_cross_thread_handoff_parents_under_submitting_trace(
            self, traced):
        """The tentpole contract: spans recorded on another thread under
        an attached handoff token parent correctly under the originating
        trace — one connected causal story across the boundary."""
        ctx = tracectx.start_trace("serving.request", model="m")
        token = ctx.handoff()

        def drain():
            with tracectx.attach(token):
                with telemetry.span("queue_wait"):
                    pass

        t = threading.Thread(target=drain, name="drain-thread", daemon=True)
        t.start()
        t.join()
        with tracectx.attach(ctx):
            with telemetry.span("resolve"):
                pass
        ctx.finish()
        doc = tracectx.get_ring().find(ctx.trace_id)
        by = _spans_by_name(doc)
        qw, = by["queue_wait"]
        res, = by["resolve"]
        root, = by["serving.request"]
        assert qw["parent_id"] == root["span_id"]
        assert res["parent_id"] == root["span_id"]
        assert qw["thread"] == "drain-thread"
        assert qw["thread"] != res["thread"]

    def test_measured_window_add_span(self, traced):
        ctx = tracectx.start_trace("req")
        t0 = time.perf_counter()
        t1 = t0 + 0.25
        ctx.add_span("queue_wait", t0, t1, reason="test")
        ctx.finish()
        doc = tracectx.get_ring().find(ctx.trace_id)
        qw, = _spans_by_name(doc)["queue_wait"]
        assert qw["dur_s"] == pytest.approx(0.25)
        assert qw["args"] == {"reason": "test"}

    def test_chrome_trace_event_carries_trace_and_span_ids(self, traced):
        """A Perfetto row and a /traces timeline cross-reference by id."""
        ctx = tracectx.start_trace("req")
        with tracectx.attach(ctx):
            with telemetry.span("work"):
                pass
        ctx.finish()
        ev = [e for e in telemetry.get_tracer().chrome_trace()["traceEvents"]
              if e.get("name") == "work"]
        assert ev and ev[-1]["args"]["trace_id"] == ctx.trace_id


# ---------------------------------------------------------------------------
# producer-thread handoff (AsyncDataSetIterator) + dangling-state closes
# ---------------------------------------------------------------------------

class _BoomSource(DataSetIterator):
    """Raises after ``good`` batches — the dying-producer fixture."""

    def __init__(self, good=0):
        self.good = good
        self._i = 0

    def reset(self):
        self._i = 0

    def __next__(self):
        if self._i >= self.good:
            raise RuntimeError("boom")
        self._i += 1
        x = np.zeros((4, 2), dtype=np.float32)
        from deeplearning4j_tpu.datasets.iterator import DataSet
        return DataSet(x, x)


class TestProducerHandoff:
    def test_producer_spans_ride_the_handoff(self, traced):
        x = np.arange(32, dtype=np.float32).reshape(8, 4)
        it = AsyncDataSetIterator(ArrayDataSetIterator(x, x, batch_size=4),
                                  trace_root="train.dispatch")
        items = list(it)
        it.close()
        assert len(items) == 2
        for item in items:
            tctx = item._trace_ctx
            assert tctx is not None
            doc = tctx.trace.to_doc()
            by = _spans_by_name(doc)
            # assembly + device placement recorded on the producer thread,
            # parented under the dispatch root the consumer will extend
            assert "etl.prefetch" in by and "etl.device_put" in by
            root, = by["train.dispatch"]
            pf, = by["etl.prefetch"]
            assert pf["parent_id"] == root["span_id"]
            assert pf["thread"] != threading.current_thread().name
            tctx.finish()
        assert tracectx.open_trace_count() == 0

    def test_no_trace_root_means_no_traces(self, traced):
        x = np.arange(32, dtype=np.float32).reshape(8, 4)
        it = AsyncDataSetIterator(ArrayDataSetIterator(x, x, batch_size=4))
        items = list(it)
        it.close()
        assert all(getattr(i, "_trace_ctx", None) is None for i in items)
        assert tracectx.open_trace_count() == 0

    def test_producer_death_mid_span_closes_its_trace(self, traced):
        it = AsyncDataSetIterator(_BoomSource(good=0),
                                  trace_root="train.dispatch")
        with pytest.raises(RuntimeError, match="boom"):
            next(iter(it))
        it.close()
        assert tracectx.open_trace_count() == 0
        # a died-mid-span trace must not masquerade as a measured slow one
        assert tracectx.get_ring().snapshot() == {}

    def test_close_abandons_queued_handoffs(self, traced):
        x = np.arange(64, dtype=np.float32).reshape(16, 4)
        it = AsyncDataSetIterator(ArrayDataSetIterator(x, x, batch_size=4),
                                  queue_size=8, trace_root="train.dispatch")
        iter(it)  # reset() starts the producer; consume nothing
        deadline = time.time() + 5
        while tracectx.open_trace_count() == 0 and time.time() < deadline:
            time.sleep(0.01)  # let the producer enqueue something
        it.close()
        assert tracectx.open_trace_count() == 0


# ---------------------------------------------------------------------------
# exemplars + exposition-format escaping
# ---------------------------------------------------------------------------

class TestExemplars:
    def test_histogram_bucket_keeps_last_trace_id(self, traced):
        h = traced.histogram("lat_seconds", buckets=(0.1, 1.0))
        a = tracectx.start_trace("req")
        with tracectx.attach(a):
            h.observe(0.5, model="m")
        a.finish()
        b = tracectx.start_trace("req")
        with tracectx.attach(b):
            h.observe(0.6, model="m")  # same bucket: b supersedes a
            h.observe(0.01, model="m")
        b.finish()
        v = traced.snapshot()["lat_seconds"]["series"][0]["value"]
        ex = v["exemplars"]
        assert ex["1.0"]["trace_id"] == b.trace_id
        assert ex["0.1"]["trace_id"] == b.trace_id
        assert ex["1.0"]["value"] == pytest.approx(0.6)

    def test_no_attached_trace_means_no_exemplars(self, traced):
        h = traced.histogram("plain_seconds")
        h.observe(0.5)
        v = traced.snapshot()["plain_seconds"]["series"][0]["value"]
        assert "exemplars" not in v

    def test_prometheus_exemplar_syntax_on_bucket_lines(self, traced):
        h = traced.histogram("lat_seconds", buckets=(0.1, 1.0))
        ctx = tracectx.start_trace("req")
        with tracectx.attach(ctx):
            h.observe(0.5, model="m")
        ctx.finish()
        text = traced.to_prometheus()
        line = [l for l in text.splitlines()
                if l.startswith("lat_seconds_bucket") and 'le="1.0"' in l]
        assert len(line) == 1
        # OpenMetrics exemplar: <bucket line> # {labels} value timestamp
        assert f'# {{trace_id="{ctx.trace_id}"}} 0.5 ' in line[0]
        # non-exemplar buckets stay plain exposition lines
        inf = [l for l in text.splitlines()
               if l.startswith("lat_seconds_bucket") and 'le="+Inf"' in l]
        assert "#" not in inf[0]

    def test_label_and_exemplar_escaping(self, traced):
        """Backslash / double-quote / newline in a label value must not
        corrupt the scrape — label values AND exemplar labels route
        through the one escaper."""
        h = traced.histogram("esc_seconds", buckets=(1.0,))
        evil = 'he said "hi"\nback\\slash'
        ctx = tracectx.start_trace("req")
        with tracectx.attach(ctx):
            h.observe(0.5, model=evil)
        ctx.finish()
        traced.counter("esc_total", "multi\nline help").inc(model=evil)
        text = traced.to_prometheus()
        for line in text.splitlines():  # escaping == no raw newlines leak
            assert not line.endswith("\\")
        assert r'model="he said \"hi\"\nback\\slash"' in text
        assert "# HELP esc_total multi\\nline help" in text
        # the exemplar survives next to the escaped label
        assert f'# {{trace_id="{ctx.trace_id}"}}' in text

    def test_jsonl_export_carries_exemplars(self, traced):
        h = traced.histogram("jl_seconds", buckets=(1.0,))
        ctx = tracectx.start_trace("req")
        with tracectx.attach(ctx):
            h.observe(0.5)
        ctx.finish()
        rows = [json.loads(l) for l in
                traced.to_jsonl().strip().splitlines()]
        hrow = [r for r in rows if r["metric"] == "jl_seconds"][0]
        assert hrow["value"]["exemplars"]["1.0"]["trace_id"] == ctx.trace_id


# ---------------------------------------------------------------------------
# slow-trace ring
# ---------------------------------------------------------------------------

def _doc(name, tid, dur):
    return {"trace_id": tid, "name": name, "duration_s": dur,
            "status": "ok", "spans": []}


class TestSlowTraceRing:
    def test_keeps_n_slowest_in_order_and_evicts_fastest(self):
        ring = SlowTraceRing(per_name=3)
        assert ring.offer(_doc("r", "a", 1.0))
        assert ring.offer(_doc("r", "b", 3.0))
        assert ring.offer(_doc("r", "c", 2.0))
        kept = ring.snapshot()["r"]
        assert [d["trace_id"] for d in kept] == ["b", "c", "a"]
        # too fast to enter a full ring
        assert not ring.offer(_doc("r", "d", 0.5))
        # slow enough: enters in order, the fastest kept ('a') is evicted
        assert ring.offer(_doc("r", "e", 2.5))
        kept = ring.snapshot()["r"]
        assert [d["trace_id"] for d in kept] == ["b", "e", "c"]

    def test_bounded_in_names_too(self):
        ring = SlowTraceRing(per_name=2, max_names=2)
        assert ring.offer(_doc("a", "1", 1.0))
        assert ring.offer(_doc("b", "2", 1.0))
        assert not ring.offer(_doc("c", "3", 99.0))  # name budget spent
        assert set(ring.snapshot()) == {"a", "b"}

    def test_find_and_named_snapshot(self):
        ring = SlowTraceRing()
        ring.offer(_doc("a", "t1", 1.0))
        ring.offer(_doc("b", "t2", 2.0))
        assert ring.find("t2")["name"] == "b"
        assert ring.find("nope") is None
        assert set(ring.snapshot("a")) == {"a"}
        assert ring.snapshot("zzz") == {}

    def test_finished_traces_ring_slowest_first(self, traced):
        slow = tracectx.start_trace("req")
        time.sleep(0.05)
        fast = tracectx.start_trace("req")
        fast.finish()
        slow.finish()
        kept = tracectx.get_ring().snapshot()["req"]
        assert kept[0]["trace_id"] == slow.trace_id
        assert kept[0]["duration_s"] >= kept[-1]["duration_s"]


# ---------------------------------------------------------------------------
# surfaces: /traces endpoint, `traces` CLI verb, flight-recorder dump
# ---------------------------------------------------------------------------

def _populate_ring(n=2):
    ids = []
    for i in range(n):
        ctx = tracectx.start_trace("serving.request", model="m")
        with tracectx.attach(ctx):
            with telemetry.span("queue_wait"):
                pass
        ctx.finish()
        ids.append(ctx.trace_id)
    return ids


class TestTraceSurfaces:
    def test_ui_traces_endpoint(self, traced):
        from deeplearning4j_tpu.ui.server import UIServer
        ids = _populate_ring()
        srv = UIServer(port=0).start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            body = json.loads(urllib.request.urlopen(
                base + "/traces").read())
            assert [d["trace_id"] for ring in body["traces"].values()
                    for d in ring]
            one = json.loads(urllib.request.urlopen(
                base + f"/traces?trace_id={ids[0]}").read())
            assert one["trace_id"] == ids[0]
            assert {s["name"] for s in one["spans"]} == {"serving.request",
                                                         "queue_wait"}
            named = json.loads(urllib.request.urlopen(
                base + "/traces?name=serving.request").read())
            assert set(named["traces"]) == {"serving.request"}
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + "/traces?trace_id=nope")
            assert ei.value.code == 404
        finally:
            srv.stop()

    def test_traces_cli_lists_and_renders_timeline(self, traced, capsys):
        from deeplearning4j_tpu.cli import main
        ids = _populate_ring()
        assert main(["traces"]) == 0
        out = capsys.readouterr().out
        assert "serving.request" in out and "queue_wait" in out
        assert main(["traces", "--trace-id", ids[0]]) == 0
        out = capsys.readouterr().out
        assert ids[0] in out
        # indented timeline: the child span renders deeper than the root
        root_line = [l for l in out.splitlines()
                     if "serving.request" in l and "trace" not in l][0]
        child_line = [l for l in out.splitlines() if "queue_wait" in l][0]
        assert (len(child_line) - len(child_line.lstrip())
                >= len(root_line) - len(root_line.lstrip()))
        assert main(["traces", "--trace-id", "nope"]) == 1

    def test_traces_cli_json_roundtrip(self, traced, capsys):
        from deeplearning4j_tpu.cli import main
        ids = _populate_ring(1)
        assert main(["traces", "--json"]) == 0
        rings = json.loads(capsys.readouterr().out)
        assert ids[0] in [d["trace_id"] for d in rings["serving.request"]]

    def test_traces_cli_reads_flight_dump_file(self, traced, capsys,
                                               tmp_path):
        """Crash forensics: the ring rides the flight dump, and the CLI
        reads it back with --file."""
        from deeplearning4j_tpu.cli import main
        ids = _populate_ring(1)
        rec = telemetry.flight.get_recorder()
        rec.note(step=0, score=1.0)
        path = rec.dump("test_anomaly", path=str(tmp_path / "dump.json"))
        with open(path) as f:
            doc = json.load(f)
        assert [d["trace_id"] for d in doc["traces"]["serving.request"]] \
            == ids
        assert main(["traces", "--file", path, "--trace-id", ids[0]]) == 0
        assert ids[0] in capsys.readouterr().out


# ---------------------------------------------------------------------------
# serving: the p99-decomposition acceptance
# ---------------------------------------------------------------------------

class TestServingTraces:
    def test_request_trace_decomposes_latency(self, traced):
        """One submitted request under load yields one connected trace
        spanning submit->queue->drain->device->resolve: every phase a
        closed child of the request's root, which covers the recorded
        latency_s."""
        from deeplearning4j_tpu.serving import ServingEngine
        net = _mlp(n_in=5, n_out=3)
        engine = ServingEngine(net, input_spec=(5,), buckets=(1, 2, 4))
        engine.start()
        try:
            xs = np.random.RandomState(0).rand(8, 5).astype(np.float32)
            futs = [engine.submit(x) for x in xs]
            for f in futs:
                f.get(timeout=30)
        finally:
            engine.stop()
        assert all(f.trace_id for f in futs)
        worst = max(futs, key=lambda f: f.latency_s)
        doc = tracectx.get_ring().find(worst.trace_id)
        assert doc is not None and doc["status"] == "ok"
        by = _spans_by_name(doc)
        for name in ("serving.queue_wait", "serving.assemble", "serving.pad",
                     "serving.aot_lookup", "serving.device_exec",
                     "serving.fetch", "serving.resolve"):
            assert name in by, f"missing child span {name}"
        # every child parents under the request root: one connected trace
        root, = by["serving.request"]
        assert root["parent_id"] is None
        parent = {s["span_id"]: s["parent_id"] for s in doc["spans"]}
        for s in doc["spans"]:
            at = s["span_id"]
            while parent[at] is not None:
                at = parent[at]
            assert at == root["span_id"], s
        # and is closed inside it. One clock, nested intervals (the trace
        # opens before submit stamps the request and closes after the
        # device is done), so this holds whatever the host's load; 1e-9
        # is the documents' rounding
        assert doc["duration_s"] is not None
        for s in doc["spans"]:
            assert s["dur_s"] is not None and s["dur_s"] >= 0, s
            assert s["t0_s"] >= 0, s
            assert s["t0_s"] + s["dur_s"] <= doc["duration_s"] + 2e-9, s
        # the root's duration is not under the latency it explains
        assert doc["duration_s"] >= worst.latency_s - 1e-9
        assert tracectx.open_trace_count() == 0

    def test_latency_histogram_tail_exemplar_links_to_ring(self, traced):
        """The acceptance chain: a histogram bucket's exemplar names a
        trace id that resolves to a complete timeline in the ring."""
        from deeplearning4j_tpu.serving import ServingEngine
        net = _mlp(n_in=5, n_out=3)
        engine = ServingEngine(net, input_spec=(5,), buckets=(1, 2))
        engine.start()
        try:
            futs = [engine.submit(
                np.random.RandomState(i).rand(5).astype(np.float32))
                for i in range(4)]
            for f in futs:
                f.get(timeout=30)
        finally:
            engine.stop()
        snap = traced.snapshot()["serving_model_latency_seconds"]
        exs = [e for s in snap["series"]
               for e in (s["value"].get("exemplars") or {}).values()]
        assert exs, "latency histogram carries no exemplars"
        submitted = {f.trace_id for f in futs}
        for e in exs:
            assert e["trace_id"] in submitted
            doc = tracectx.get_ring().find(e["trace_id"])
            assert doc is not None
            assert "serving.queue_wait" in _spans_by_name(doc)

    def test_shed_request_trace_rings_with_status(self, traced):
        from deeplearning4j_tpu.serving import ServingEngine, \
            ServingOverloaded
        net = _mlp(n_in=5, n_out=3)
        engine = ServingEngine(net, input_spec=(5,), buckets=(4,),
                               max_queue=2)  # never started: queue fills
        x = np.zeros((1, 5), dtype=np.float32)
        futs = [engine.submit(x) for _ in range(2)]
        with pytest.raises(ServingOverloaded):
            engine.submit(x)
        shed = [d for d in tracectx.get_ring().snapshot().get(
            "serving.request", []) if d["status"] == "shed"]
        assert len(shed) == 1
        by = _spans_by_name(shed[0])
        assert by["serving.shed"][0]["args"]["reason"] == "queue_full"
        engine.stop()  # drains the queue, abandoning the 2 queued traces
        assert all(f.done() for f in futs)
        assert tracectx.open_trace_count() == 0

    def test_direct_path_rings_under_its_own_root(self, traced):
        from deeplearning4j_tpu.serving import ServingEngine
        net = _mlp(n_in=5, n_out=3)
        engine = ServingEngine(net, input_spec=(5,), buckets=(4,))
        engine.output(np.zeros((2, 5), dtype=np.float32))
        rings = tracectx.get_ring().snapshot()
        assert "serving.request_direct" in rings
        assert "serving.request" not in rings  # no fake queue-wait story


# ---------------------------------------------------------------------------
# training: fused dispatch + plain step traces
# ---------------------------------------------------------------------------

class TestTrainingTraces:
    def test_fused_fit_connects_producer_and_dispatch_threads(self, traced):
        net = _mlp()
        x, y = _xy(n=32)
        net.fit(x, y, epochs=2, batch_size=8, steps_per_dispatch=2)
        docs = tracectx.get_ring().snapshot().get("train.dispatch", [])
        assert docs, "fused fit rang no dispatch traces"
        threads = set()
        for doc in docs:
            by = _spans_by_name(doc)
            assert "etl.prefetch" in by  # producer-thread assembly
            assert "fit.step" in by      # consumer-thread dispatch
            threads.add(by["etl.prefetch"][0]["thread"])
            threads.add(by["fit.step"][0]["thread"])
            root, = by["train.dispatch"]
            assert by["fit.step"][0]["parent_id"] == root["span_id"]
        assert len(threads) >= 2, "producer and dispatch ran on one thread"
        # the one-late score fetch lands in the PREVIOUS dispatch's trace
        fetched = [d for d in docs
                   if "train.score_fetch" in _spans_by_name(d)]
        assert fetched
        assert tracectx.open_trace_count() == 0

    def test_plain_fit_steps_ring_and_close(self, traced):
        net = _mlp()
        x, y = _xy(n=32)
        net.fit(x, y, epochs=1, batch_size=8)
        docs = tracectx.get_ring().snapshot().get("train.step", [])
        assert docs
        by = _spans_by_name(docs[0])
        assert "fit.etl" in by and "fit.step" in by
        root, = by["train.step"]
        assert by["fit.etl"][0]["parent_id"] == root["span_id"]
        assert tracectx.open_trace_count() == 0

    def test_step_records_stamp_trace_id(self, traced):
        net = _mlp()
        x, y = _xy(n=32)
        net.fit(x, y, epochs=1, batch_size=8)
        recs = telemetry.flight.get_recorder().snapshot()
        assert recs
        with_id = [r for r in recs if r.get("trace_id")]
        assert with_id, "flight records carry no trace_id"
        rung = {d["trace_id"] for d in
                tracectx.get_ring().snapshot().get("train.step", [])}
        assert rung & {r["trace_id"] for r in with_id}

    def test_crashed_fit_leaves_no_open_trace(self, traced):
        net = _mlp()
        x, y = _xy(n=32)
        bad_y = np.zeros((32, 3), dtype=np.float32)  # wrong label width
        with pytest.raises(Exception):
            net.fit(x, bad_y, epochs=1, batch_size=8)
        assert tracectx.open_trace_count() == 0


# ---------------------------------------------------------------------------
# disabled-mode overhead: the step path must not touch contextvars
# ---------------------------------------------------------------------------

class _PoisonVar:
    """A contextvar stand-in that fails the test on ANY access — proves
    the disabled path is an attribute read and a branch, nothing more."""

    def get(self, *a):
        raise AssertionError("contextvar read on the disabled path")

    def set(self, *a):
        raise AssertionError("contextvar write on the disabled path")

    def reset(self, *a):
        raise AssertionError("contextvar reset on the disabled path")


class TestDisabledOverhead:
    def test_disabled_api_never_touches_the_contextvar(self, monkeypatch):
        monkeypatch.setattr(tracectx, "_cvar", _PoisonVar())
        assert tracectx.maybe_start("x") is None
        assert tracectx.current() is None
        assert tracectx.current_trace_id() is None
        with tracectx.attach(None):
            pass
        with telemetry.span("s"):  # disabled span: shared no-op object
            pass
        h = telemetry.get_registry().histogram("h_seconds")
        h.observe(0.1)  # exemplar source consulted only when tracing is on

    def test_disabled_fit_never_touches_the_contextvar(self, monkeypatch):
        """The whole instrumented step path (fit loop, scorepipe, async
        prefetch) with tracing off: zero contextvar ops, zero traces."""
        monkeypatch.setattr(tracectx, "_cvar", _PoisonVar())
        net = _mlp()
        x, y = _xy(n=16)
        net.fit(x, y, epochs=1, batch_size=8)
        net.fit(x, y, epochs=1, batch_size=8, steps_per_dispatch=2)
        assert tracectx.open_trace_count() == 0
        assert tracectx.get_ring().snapshot() == {}

    def test_disabled_overhead_smoke(self):
        # a tripwire, not a benchmark: 30k disabled maybe_start/attach
        # pairs must stay branch-cheap (sub-second leaves ~30us/op of
        # headroom, orders of magnitude above the intended cost)
        t0 = time.perf_counter()
        for _ in range(30000):
            ctx = tracectx.maybe_start("x")
            with tracectx.attach(ctx):
                pass
        assert time.perf_counter() - t0 < 1.0


# ---------------------------------------------------------------------------
# graftsan: the tracer's own bookkeeping holds tracked locks
# ---------------------------------------------------------------------------

class TestGraftsanClean:
    def test_trace_mutation_is_lock_protected_under_graftsan(self):
        """Cross-thread span recording into one Trace happens under the
        trace's own threading.Lock — a *tracked* lock under graftsan, so
        watch_rmw sees no unlocked cross-thread read-modify-write and the
        held-stack stays balanced (no lock-inversion/leak findings from
        the tracer's internals)."""
        from deeplearning4j_tpu.analysis.sanitizer import Sanitizer
        with Sanitizer() as san:
            telemetry.enable()
            try:
                ctx = tracectx.start_trace("req")
                assert san.watch_rmw(ctx.trace, "spans", "finished",
                                     "_nspan")
                token = ctx.handoff()

                def worker():
                    with tracectx.attach(token):
                        with telemetry.span("w"):
                            pass

                ts = [threading.Thread(target=worker, daemon=True)
                      for _ in range(4)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                ctx.finish()
                tracectx.get_ring().clear()
            finally:
                telemetry.disable()
        san_findings = [f for f in san.check()
                        if f.kind in ("unlocked-rmw", "lock-inversion")]
        assert san_findings == [], [f.human() for f in san_findings]
