"""2-D (batch × seq) shape-bucketing tests: ShapeBuckets grid properties,
seq-axis padding/masking, the seq-aware serving engine (parity vs the
unbucketed forward, zero lazy compiles on a warmed grid, token-fill and
seq-length series, seq/padded token metering), warm-manifest invalidation
on a grid change, the registry's A/B grid persistence + counted bundle
rejection, per-seq-bucket flash-vs-XLA crossover consultation, and the
seq-aware fleet wire (seq-uniform chunks, seq_len cross-check, varied-seq
canaries)."""

import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu import serving as serving_pkg
from deeplearning4j_tpu.datasets.iterator import (BucketRegistry,
                                                  ShapeBuckets, pad_batch,
                                                  seq_edges_from_demand,
                                                  validity_mask)
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn.conf import inputs as I
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving import ServingEngine
from deeplearning4j_tpu.serving import metering as _metering
from deeplearning4j_tpu.serving.registry import (ModelRegistry,
                                                 manifest_grid_signatures)


@pytest.fixture(autouse=True)
def _isolate():
    telemetry.reset()
    telemetry.disable()
    serving_pkg.reset()
    yield
    serving_pkg.reset()
    telemetry.reset()
    telemetry.disable()


@pytest.fixture
def fresh(_isolate):
    reg = telemetry.get_registry()
    telemetry.enable()
    yield reg


def _rnn(seed=7, n_in=4, n_out=3, t=32):
    net = MultiLayerNetwork(NeuralNetConfig(seed=seed).list(
        L.SimpleRnn(n_out=6),
        L.RnnOutputLayer(n_out=n_out, loss="mcxent"),
        input_type=I.RecurrentType(n_in, t),
    ))
    net.init()
    return net


def _xs(n, t, n_in=4, seed=0):
    return np.random.default_rng(seed).standard_normal(
        (n, t, n_in)).astype(np.float32)


# ---------------------------------------------------------------------------
# ShapeBuckets grid
# ---------------------------------------------------------------------------

class TestShapeBuckets:
    def test_bucket_for_covers_request(self):
        g = ShapeBuckets([1, 2, 8], [16, 64, 256])
        assert g.bucket_for(1, 1) == (1, 16)
        assert g.bucket_for(2, 16) == (2, 16)
        assert g.bucket_for(3, 17) == (8, 64)
        assert g.bucket_for(8, 256) == (8, 256)

    def test_bucket_for_none_past_max(self):
        g = ShapeBuckets([1, 2], [16, 32])
        assert g.bucket_for(3, 16) is None     # batch overflow
        assert g.bucket_for(1, 33) is None     # seq overflow
        assert g.bucket_for(3, 33) is None     # both

    def test_bucket_for_properties(self):
        """Pseudo-property sweep: the chosen bucket always covers the
        request on BOTH axes, and growing a request never shrinks its
        bucket (monotonicity per axis)."""
        g = ShapeBuckets([1, 3, 8, 32], [8, 48, 128])
        rng = np.random.default_rng(0)
        cases = [(int(r), int(s))
                 for r, s in zip(rng.integers(1, 33, 200),
                                 rng.integers(1, 129, 200))]
        for rows, seq in cases:
            b, s = g.bucket_for(rows, seq)
            assert b >= rows and s >= seq
            assert b in g.batch.sizes() and s in g.seq.sizes()
            # monotone: a strictly smaller request maps no higher
            b2, s2 = g.bucket_for(max(1, rows - 1), max(1, seq - 1))
            assert b2 <= b and s2 <= s

    def test_round_up_to_multiple_touches_batch_only(self):
        g = ShapeBuckets([1, 2, 5], [16, 48])
        r = g.round_up_to_multiple(4)
        assert r.batch.sizes() == [4, 8]       # 1,2 -> 4 (merged), 5 -> 8
        assert r.seq.sizes() == [16, 48]       # seq axis untouched
        assert r.bucket_for(3, 20) == (4, 48)

    def test_powers_of_two_grid(self):
        g = ShapeBuckets.powers_of_two(8, 128)
        assert g.batch.sizes() == [1, 2, 4, 8]
        assert g.seq.sizes() == [16, 32, 64, 128]
        assert g.max == 8 and g.max_seq == 128
        tiny = ShapeBuckets.powers_of_two(2, 8)   # min_seq clamps to max
        assert tiny.seq.sizes() == [8]

    def test_signature_iter_len(self):
        g = ShapeBuckets([2, 1], [32, 16])
        assert g.signature() == "b=1,2;s=16,32"
        assert len(g) == 4
        assert list(g) == [(1, 16), (1, 32), (2, 16), (2, 32)]
        assert g.sizes() == list(g)

    def test_with_batch_keeps_seq(self):
        g = ShapeBuckets([1, 2], [16, 32])
        h = g.with_batch([4])
        assert h.batch.sizes() == [4] and h.seq.sizes() == [16, 32]

    def test_from_demand_falls_back_cold(self, fresh):
        g = ShapeBuckets.from_demand([1, 2], 128)
        assert g.seq.sizes() == [16, 32, 64, 128]  # powers-of-two fallback


class TestSeqEdgesFromDemand:
    # a PRIVATE registry per test: telemetry.reset() keeps metric
    # definitions (histogram bounds included), so registering the
    # engine's series name with test-sized buckets on the process
    # default would poison every later engine construction

    def test_edges_from_history(self):
        from deeplearning4j_tpu.telemetry.history import MetricsHistory
        from deeplearning4j_tpu.telemetry.registry import MetricsRegistry
        reg = MetricsRegistry()
        h = reg.histogram(
            "serving_request_seq_len", "test lengths",
            buckets=(16, 32, 64, 128, 256))
        for t in [10] * 60 + [100] * 30 + [250] * 10:
            h.observe(t, model="m")
        hist = MetricsHistory(reg)
        hist.sample_now()
        edges = seq_edges_from_demand(256, history=hist)
        # p50 lands in le=16, p90 in le=128; max_seq always included
        assert edges == [16, 128, 256]

    def test_no_samples_is_none(self):
        from deeplearning4j_tpu.telemetry.history import MetricsHistory
        from deeplearning4j_tpu.telemetry.registry import MetricsRegistry
        hist = MetricsHistory(MetricsRegistry())
        hist.sample_now()
        assert seq_edges_from_demand(256, history=hist) is None

    def test_edges_clamped_to_max_seq(self):
        from deeplearning4j_tpu.telemetry.history import MetricsHistory
        from deeplearning4j_tpu.telemetry.registry import MetricsRegistry
        reg = MetricsRegistry()
        h = reg.histogram("serving_request_seq_len", "test lengths",
                          buckets=(16, 512))
        for t in [400] * 10:
            h.observe(t)
        hist = MetricsHistory(reg)
        hist.sample_now()
        assert seq_edges_from_demand(128, history=hist) == [128]


# ---------------------------------------------------------------------------
# seq-axis padding + masking
# ---------------------------------------------------------------------------

class TestPadBatchSeq:
    def test_pads_rows_and_steps_with_exact_mask(self):
        x = _xs(3, 5)
        y = np.ones((3, 5, 2), np.float32)
        xp, yp, m, n = pad_batch(x, y, None, 4, seq_target=8)
        assert xp.shape == (4, 8, 4) and yp.shape == (4, 8, 2)
        assert n == 3 and m.shape == (4, 8)
        assert m[:3, :5].all() and m[3:].sum() == 0 and m[:, 5:].sum() == 0
        np.testing.assert_array_equal(xp[:3, :5], x)
        assert float(np.abs(xp[:, 5:]).sum()) == 0.0

    def test_class_labels_not_stretched(self):
        x = _xs(2, 6)
        y = np.eye(3, dtype=np.float32)[:2]    # [B, C] — no time axis
        xp, yp, m, n = pad_batch(x, y, None, 4, seq_target=8)
        assert xp.shape == (4, 8, 4)
        assert yp.shape == (4, 3)              # untouched by the seq pad
        assert m.shape == (4,) and m[:2].all() and m[2:].sum() == 0

    def test_given_mask_padded_on_both_axes(self):
        x = _xs(3, 5)
        y = np.ones((3, 5, 2), np.float32)
        m_in = np.ones((3, 5), np.float32)
        _xp, _yp, m, _n = pad_batch(x, y, m_in, 4, seq_target=8)
        assert m.shape == (4, 8)
        assert m[:3, :5].all() and float(m.sum()) == 15.0

    def test_oversize_seq_raises(self):
        x = _xs(2, 10)
        with pytest.raises(ValueError, match="exceeds the bucketed"):
            pad_batch(x, np.ones((2, 10, 2), np.float32), None, 2,
                      seq_target=8)

    def test_validity_mask_seq_axis(self):
        y = np.ones((2, 5, 3), np.float32)
        m = validity_mask(y, 1, 2, seq_valid=5, seq_target=8)
        assert m.shape == (2, 8)
        assert m[0, :5].all() and m[0, 5:].sum() == 0 and m[1].sum() == 0


# ---------------------------------------------------------------------------
# seq-aware serving engine
# ---------------------------------------------------------------------------

class TestSeqAwareEngine:
    def test_parity_and_zero_lazy_compiles(self, fresh):
        net = _rnn()
        eng = ServingEngine(net, name="seqeng", input_spec=(32, 4),
                            buckets=(1, 2), seq_buckets=(8, 16, 32),
                            batch_window_s=0.0)
        eng.start()
        try:
            for seed, (n, t) in enumerate([(1, 5), (2, 11), (2, 32),
                                           (1, 8), (2, 16)]):
                x = _xs(n, t, seed=seed)
                got = np.asarray(eng.submit(x, batched=True).get(timeout=30))
                want = np.asarray(net.output(x))
                assert got.shape == want.shape
                assert float(np.max(np.abs(got - want))) <= 1e-6
            aot = eng.stats()["aot"]
            assert aot["warmed"] == 6           # 2 batch x 3 seq
            assert aot["lazy_compiles"] == 0    # every request on-grid
            assert eng.stats()["buckets"] == [1, 2]
            assert eng.stats()["seq_buckets"] == [8, 16, 32]
        finally:
            eng.stop()

    def test_direct_output_parity(self, fresh):
        net = _rnn()
        eng = ServingEngine(net, name="seqdirect", input_spec=(32, 4),
                            buckets=(1, 2), seq_buckets=(8, 32))
        x = _xs(2, 20, seed=3)
        got = np.asarray(eng.output(x))
        want = np.asarray(net.output(x))
        assert float(np.max(np.abs(got - want))) <= 1e-6

    def test_oversize_seq_rejected_not_chunked(self, fresh):
        net = _rnn()
        eng = ServingEngine(net, name="seqmax", input_spec=(16, 4),
                            buckets=(1, 2), seq_buckets=(8, 16))
        with pytest.raises(ValueError, match="cannot be chunked"):
            eng.output(_xs(1, 20))
        eng.start()
        try:
            with pytest.raises(ValueError, match="exceeds the largest"):
                eng.submit(_xs(1, 20), batched=True)
        finally:
            eng.stop()

    def test_token_fill_and_seq_len_series(self, fresh):
        net = _rnn()
        eng = ServingEngine(net, name="seqfill", input_spec=(32, 4),
                            buckets=(1, 2), seq_buckets=(8, 32),
                            batch_window_s=0.0)
        eng.start()
        try:
            eng.submit(_xs(1, 5), batched=True).get(timeout=30)
        finally:
            eng.stop()
        snap = fresh.snapshot()
        tf = snap["serving_batch_token_fill_ratio"]["series"]
        assert len(tf) == 1
        # 1 row x 5 steps into a (1, 8) shape: token fill 5/8
        assert abs(tf[0]["value"]["sum"] - 5.0 / 8.0) < 1e-9
        sl = snap["serving_request_seq_len"]["series"]
        assert sl and sl[0]["value"]["sum"] == 5.0

    def test_metering_charges_padded_tokens(self, fresh):
        net = _rnn()
        eng = ServingEngine(net, name="seqmeter", input_spec=(32, 4),
                            buckets=(1, 2), seq_buckets=(8, 32),
                            batch_window_s=0.0)
        eng.start()
        try:
            eng.submit(_xs(2, 20, seed=1), batched=True).get(timeout=30)
        finally:
            eng.stop()
        usage = _metering.get_meter().usage()["models"]["seqmeter"]
        assert usage["rows"] == 2
        assert usage["seq_tokens"] == 40        # 2 rows x 20 real steps
        assert usage["padded_tokens"] == 64     # (2, 32) device shape
        # FLOPs charged at padded tokens, not padded rows x max_seq
        params = sum(int(np.prod(np.shape(p)))
                     for p in jax.tree_util.tree_leaves(net.params))
        assert usage["flops"] == pytest.approx(2.0 * params * 64)


# ---------------------------------------------------------------------------
# warm manifest: the grid is part of the executable's identity
# ---------------------------------------------------------------------------

class TestWarmManifestGrid:
    def test_manifest_kind_carries_grid(self, fresh):
        net = _rnn()
        eng = ServingEngine(net, name="kind", input_spec=(16, 4),
                            buckets=(1,), seq_buckets=(8, 16))
        assert eng._fwd._manifest_kind.endswith(":grid=b=1;s=8,16")
        flat = ServingEngine(net, name="kindflat", input_spec=(16, 4),
                             buckets=(1,))
        assert ":grid=" not in flat._fwd._manifest_kind

    def test_seq_grid_change_invalidates_manifest(self, fresh):
        net = _rnn()
        e1 = ServingEngine(net, name="wm1", input_spec=(16, 4),
                           buckets=(1,), seq_buckets=(8, 16))
        m = e1.export_warm_manifest()
        if m is None:
            pytest.skip("backend cannot serialize executables")
        assert manifest_grid_signatures(m) == {"b=1;s=8,16"}
        # same grid: every bucket restores from the manifest
        e2 = ServingEngine(net, name="wm2", input_spec=(16, 4),
                           buckets=(1,), seq_buckets=(8, 16),
                           warm_manifest=m)
        aot = e2.stats()["aot"]
        assert aot["manifest_hits"] == 2 and aot["manifest_misses"] == 0
        # changed seq grid: ZERO resurrected executables, all misses
        e3 = ServingEngine(net, name="wm3", input_spec=(16, 4),
                           buckets=(1,), seq_buckets=(4, 16),
                           warm_manifest=m)
        aot3 = e3.stats()["aot"]
        assert aot3["manifest_hits"] == 0 and aot3["manifest_misses"] == 2


# ---------------------------------------------------------------------------
# registry: per-model grid persistence + counted bundle rejection
# ---------------------------------------------------------------------------

class TestRegistryGrid:
    def test_register_like_carries_grid(self, fresh):
        reg = ModelRegistry()
        try:
            e1 = reg.register("champ", _rnn(1), input_spec=(16, 4),
                              buckets=(1, 2), seq_buckets=(8, 16),
                              start=False)
            e2 = reg.register_like("champ", "challenger", _rnn(2),
                                   start=False)
            assert e2._fwd.seq_aware
            assert (e2._fwd.buckets.signature()
                    == e1._fwd.buckets.signature())
            kw = reg.engine_kwargs("champ")
            assert kw["seq_buckets"] == (8, 16)
            kw["seq_buckets"] = None            # a copy, not the record
            assert reg.engine_kwargs("champ")["seq_buckets"] == (8, 16)
        finally:
            reg.stop()

    def test_bundle_grid_mismatch_rejected_counted(self, fresh):
        net = _rnn(1)
        reg = ModelRegistry()
        try:
            reg.register("m", net, input_spec=(16, 4), buckets=(1,),
                         seq_buckets=(8, 16), start=False)
            other = ServingEngine(net, name="other", input_spec=(16, 4),
                                  buckets=(1,), seq_buckets=(4, 16))
            m = other.export_warm_manifest()
            if m is None:
                pytest.skip("backend cannot serialize executables")
            with pytest.raises(ValueError, match="grid"):
                reg.update_model("m", _rnn(2), manifest=m)
            snap = fresh.snapshot()
            series = snap["serving_bundle_rejected_total"]["series"]
            assert [s for s in series
                    if s["labels"] == {"model": "m",
                                       "reason": "grid_mismatch"}
                    and s["value"] == 1.0]
            # a matching bundle still swaps
            ok = reg.engine("m").export_warm_manifest()
            if ok is not None:
                reg.update_model("m", _rnn(3), manifest=ok)
        finally:
            reg.stop()

    def test_grid_signatures_reader(self):
        class FakeManifest:
            def keys(self):
                return [("serving:grid=b=1;s=8", "sig1"),
                        ("serving", "sig2"),
                        ("train", "sig3")]
        assert manifest_grid_signatures(FakeManifest()) == \
            {"b=1;s=8", None}


# ---------------------------------------------------------------------------
# flash-vs-XLA crossover: consulted per seq bucket, not at max_seq
# ---------------------------------------------------------------------------

class TestCrossoverPerSeqBucket:
    def test_resolve_verdict_differs_across_buckets(self, monkeypatch):
        from deeplearning4j_tpu.ops import attention_pallas as _ap
        monkeypatch.setattr(_ap, "backend_is_tpu", lambda: True)
        shape = lambda t: (2, t, 8, 64)  # noqa: E731
        short = _ap.resolve_attention(shape(128), shape(128), None,
                                      jnp.float32)
        long_ = _ap.resolve_attention(shape(2048), shape(2048), None,
                                      jnp.float32)
        assert short is None          # naive XLA below the crossover
        assert long_ is not None      # flash geometry above it

    def test_each_seq_bucket_traces_its_own_consultation(self, monkeypatch):
        """Per-(batch, seq) executables call the dispatch resolver at
        trace time with THEIR seq — a 2-D grid consults the crossover
        per bucket, where the 1-D registry asked once at max_seq."""
        from deeplearning4j_tpu.nn.layers import attention as _attn
        from deeplearning4j_tpu.ops import attention_pallas as _ap
        seen = []

        def spy(q_shape, k_shape, mask, dtype, geometry=None):
            seen.append(int(q_shape[1]))
            return None               # always take the naive (CPU) path

        monkeypatch.setattr(_ap, "resolve_attention", spy)
        seq_grid = (128, 512, 2048)
        for t in seq_grid:
            q = jax.ShapeDtypeStruct((1, t, 2, 16), jnp.float32)
            jax.jit(lambda q, k, v: _attn.dot_product_attention(
                q, k, v)).lower(q, q, q)
        assert seen == list(seq_grid)


# ---------------------------------------------------------------------------
# fleet wire: seq-uniform chunks, seq_len cross-check, varied-seq canaries
# ---------------------------------------------------------------------------

class TestFleetSeqWire:
    @pytest.fixture
    def fleet(self, fresh):
        from deeplearning4j_tpu.fleet import FleetRouter, FleetWorker
        net = _rnn()
        eng = ServingEngine(net, name="seqfleet", input_spec=(32, 4),
                            buckets=(1, 2, 4), seq_buckets=(8, 16, 32),
                            batch_window_s=0.0)
        worker = FleetWorker(eng, worker_id="w0").start()
        router = FleetRouter([("w0", worker.address)], name="seqfleet",
                             seq_aware=True, batch_window_s=0.0)
        yield net, eng, worker, router
        router.stop()
        worker.stop()

    def test_mixed_lengths_parity_through_wire(self, fleet):
        net, eng, _worker, router = fleet
        futs = []
        for seed, t in [(1, 5), (2, 30), (3, 5), (4, 12)]:
            x = _xs(1, t, seed=seed)[0]
            futs.append((x, router.submit(x)))
        for x, f in futs:
            got = np.asarray(f.get(timeout=30))
            want = np.asarray(net.output(x[None]))[0]
            assert float(np.max(np.abs(got - want))) <= 1e-6
        assert eng.stats()["aot"]["lazy_compiles"] == 0

    def test_seq_rides_meta_for_chunking(self, fleet):
        _net, _eng, _worker, router = fleet
        fut = router.submit(_xs(1, 12, seed=5), batched=True)
        fut.get(timeout=30)
        # seq-aware submit folds the length into the entry meta — the
        # chunk-uniformity seam that keeps wire payloads rectangular
        with pytest.raises(ValueError, match="no sequence axis"):
            router.submit(np.zeros((), np.float32))

    def test_worker_rejects_seq_len_mismatch(self, fleet):
        _net, _eng, worker, _router = fleet
        x = _xs(1, 12, seed=6)
        payload = json.dumps({"rows": x.tolist(), "seq_len": 16}).encode()
        req = urllib.request.Request(
            worker.address + "/submit", data=payload,
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 400
        assert "seq_len" in ei.value.read().decode()

    def test_seq_sweep_canaries(self, fleet):
        from deeplearning4j_tpu.fleet import seq_sweep_canaries
        from deeplearning4j_tpu.fleet.prober import FleetProber
        net, _eng, _worker, router = fleet
        canaries = seq_sweep_canaries(net.output, (4,), (8, 16, 32),
                                      model="seqfleet")
        assert [c["x"].shape[0] for c in canaries] == [8, 15, 32]
        prober = FleetProber(router, canaries, interval_s=999.0)
        results = prober.probe_once()
        assert [r["verdict"] for r in results] == ["ok"] * 3

    def test_worker_describe_ships_seq_grid(self, fleet):
        _net, _eng, worker, _router = fleet
        doc = worker.describe()
        assert doc["buckets"] == [1, 2, 4]
        assert doc["seq_buckets"] == [8, 16, 32]
