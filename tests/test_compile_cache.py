"""Compile-artifact cache tier (utils/compile_cache, ISSUE 9): persistent
XLA cache wiring, warm AOT manifests, the one-zip resumable bundle, and the
instant-restart acceptance claims — a warm restart performs ZERO compiles
for manifest-covered signatures, and crash→resume (checkpoint + opt_state +
RNG chain + buckets + manifest as one unit) is bit-exact vs an
uninterrupted run."""

import json
import os
import zipfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.datasets.iterator import BucketRegistry
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn import updaters as U
from deeplearning4j_tpu.nn.conf import inputs as I
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving.engine import ServingEngine
from deeplearning4j_tpu.utils import compile_cache as cc
from deeplearning4j_tpu.utils.serialization import (load_bundle, load_model,
                                                    save_bundle, save_model)


@pytest.fixture(autouse=True)
def _isolate():
    # the persistent-cache directory itself is isolated per test by
    # conftest._isolated_compile_cache
    telemetry.reset()
    yield
    telemetry.reset()
    telemetry.disable()


def _mlp(n_in=8, n_out=4, hidden=16, seed=3, dropout=0.0):
    net = MultiLayerNetwork(
        NeuralNetConfig(seed=seed, dropout=dropout,
                        updater=U.Adam(learning_rate=1e-3)).list(
            L.DenseLayer(n_out=hidden, activation="relu"),
            L.OutputLayer(n_out=n_out, loss="mcxent"),
            input_type=I.FeedForwardType(n_in)))
    net.init()
    return net


def _data(n=48, n_in=8, n_out=4, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.rand(n, n_in).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[rs.randint(0, n_out, n)]
    return x, y


def _leaves_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return all(bool((np.asarray(x) == np.asarray(y)).all())
               for x, y in zip(la, lb))


def _counter_total(name, **labels):
    c = telemetry.get_registry().get(name)
    if c is None:
        return 0.0
    return sum(c.value(**ls) for ls in c.labelsets()
               if all(ls.get(k) == v for k, v in labels.items()))


# ---------------------------------------------------------------------------
# persistent compilation cache (tier a)
# ---------------------------------------------------------------------------

class TestPersistentCache:
    """One rule: $JAX_COMPILATION_CACHE_DIR when set (and then no directory
    is set in code), else <checkout>/.jax_cache."""

    def test_env_dir_wins_and_no_directory_is_set_in_code(self, tmp_path,
                                                          monkeypatch):
        d = str(tmp_path / "envcache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
        before = jax.config.jax_compilation_cache_dir
        assert cc.enable_persistent_cache() == d
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_fixed_dir_under_the_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert cc.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
        assert cc.enable_persistent_cache() == cc.DEFAULT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == cc.DEFAULT_CACHE_DIR

    def test_thresholds_open_so_small_compiles_land_on_disk(self, tmp_path):
        d = str(tmp_path / "xc")
        # what the environment variable does when set before jax starts
        jax.config.update("jax_compilation_cache_dir", d)
        cc.enable_persistent_cache()

        @jax.jit
        def f(x):
            return x * 3.0
        f(jnp.ones(7)).block_until_ready()
        assert os.listdir(d), "no cache entry written for a fresh compile"

    def test_manifest_bound_compile_bypasses_a_warm_cache(self, tmp_path):
        """An executable served from the persistent cache cannot be
        re-serialized on the CPU backend (loads, then NOT_FOUND at call):
        aot_compile with a manifest must compile fresh even when the cache
        already holds the program, and the restored executable must RUN."""
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / "xc"))
        cc.enable_persistent_cache()

        def f(x, w):
            return jnp.tanh(x @ w).sum()
        x, w = jnp.ones((8, 16)), jnp.ones((16, 4))
        jax.jit(f).lower(x, w).compile()   # populate the cache  # graftlint: disable=R3
        n = len(os.listdir(str(tmp_path / "xc")))
        assert n > 0
        man = cc.WarmManifest(model_fp="t")
        cc.aot_compile(jax.jit(f), x, w, manifest=man, kind="t")
        assert len(os.listdir(str(tmp_path / "xc"))) == n  # not written
        restored = cc.WarmManifest.from_bytes(man.to_bytes()) \
            .load_executable("t", cc.signature_of((x, w)))
        assert float(restored(x, w)) == float(f(x, w))
        # the cache is back on afterwards
        jax.jit(lambda a: a - 7.0)(jnp.ones(3)).block_until_ready()
        assert len(os.listdir(str(tmp_path / "xc"))) > n


# ---------------------------------------------------------------------------
# fingerprints + signatures
# ---------------------------------------------------------------------------

class TestFingerprints:
    def test_same_architecture_same_fingerprint(self):
        assert cc.model_fingerprint(_mlp()) == cc.model_fingerprint(_mlp())

    def test_different_architecture_differs(self):
        assert cc.model_fingerprint(_mlp()) != \
            cc.model_fingerprint(_mlp(hidden=32))

    def test_value_free_retrained_net_matches(self):
        # XLA executables depend on shapes, not weights: a retrained
        # checkpoint of the same architecture reuses its manifest
        net = _mlp()
        fp0 = cc.model_fingerprint(net)
        x, y = _data()
        net.fit(x, y, epochs=1, batch_size=16)
        assert cc.model_fingerprint(net) == fp0

    def test_signature_of_shapes_and_dtypes(self):
        a = cc.signature_of((jnp.ones((2, 3)), jnp.ones(4, jnp.int32)))
        b = cc.signature_of((jnp.ones((2, 3)), jnp.ones(4, jnp.int32)))
        c = cc.signature_of((jnp.ones((2, 4)), jnp.ones(4, jnp.int32)))
        assert a == b and a != c

    def test_signature_distinguishes_tree_structure(self):
        a = cc.signature_of(({"x": jnp.ones(3)},))
        b = cc.signature_of((jnp.ones(3),))
        assert a != b


# ---------------------------------------------------------------------------
# warm manifest (tier b)
# ---------------------------------------------------------------------------

class TestWarmManifest:
    def _compiled(self):
        f = jax.jit(lambda x: x * 2.0)
        return f.lower(jnp.ones(6)).compile()  # graftlint: disable=R3 -- building the raw executable the manifest tests serialize

    def test_put_and_load_roundtrip(self):
        telemetry.enable()
        m = cc.WarmManifest("model", "backend-x")
        assert m.put("k", "sig", self._compiled())
        ex = m.load_executable("k", "sig")
        assert ex is not None
        np.testing.assert_allclose(np.asarray(ex(jnp.ones(6))), 2.0)
        ev = cc.event_counts()
        assert ev.get("serialize") == 1 and ev.get("hit") == 1

    def test_missing_entry_counts_miss(self):
        telemetry.enable()
        m = cc.WarmManifest()
        assert m.load_executable("k", "nope") is None
        assert cc.event_counts().get("miss") == 1

    def test_load_lenient_missing_file_is_silent_none(self, tmp_path):
        # a not-yet-created manifest is the normal FIRST cold start —
        # no corruption warning, no deserialize_fail (that counter means
        # a poisoned artifact, and the coldstart gate reads it)
        telemetry.enable()
        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("error")
            assert cc.WarmManifest.load_lenient(
                str(tmp_path / "nope.zip")) is None
        assert not cc.event_counts().get("deserialize_fail")

    def test_load_lenient_corrupt_file_warns_and_counts(self, tmp_path):
        telemetry.enable()
        bad = tmp_path / "bad.zip"
        bad.write_bytes(b"\x00junk")
        with pytest.warns(UserWarning, match="unreadable"):
            assert cc.WarmManifest.load_lenient(str(bad)) is None
        assert cc.event_counts().get("deserialize_fail") == 1

    def test_corrupt_entry_counts_deserialize_fail(self):
        telemetry.enable()
        m = cc.WarmManifest()
        with m._mlock:
            m._entries[("k", "sig")] = b"not a pickle"
        assert m.load_executable("k", "sig") is None
        assert cc.event_counts().get("deserialize_fail") == 1

    def test_save_load_zip(self, tmp_path):
        m = cc.WarmManifest("mfp", "bfp")
        m.put("serving", "s1", self._compiled())
        p = m.save(str(tmp_path / "wm.zip"))
        m2 = cc.WarmManifest.load(p)
        assert m2.model_fp == "mfp" and m2.backend_fp == "bfp"
        assert m2.keys() == [("serving", "s1")]
        assert m2.load_executable("serving", "s1") is not None

    def test_bytes_roundtrip(self):
        m = cc.WarmManifest("mfp")
        m.put("k", "s", self._compiled())
        m2 = cc.WarmManifest.from_bytes(m.to_bytes())
        assert len(m2) == 1 and m2.backend_fp == m.backend_fp

    def test_newer_version_refused(self, tmp_path):
        p = str(tmp_path / "future.zip")
        with zipfile.ZipFile(p, "w") as z:
            z.writestr("manifest.json", json.dumps(
                {"manifest_version": cc.MANIFEST_VERSION + 1,
                 "entries": []}))
        with pytest.raises(ValueError, match="newer"):
            cc.WarmManifest.load(p)

    def test_matches_gates_model_and_backend(self):
        net = _mlp()
        m = cc.WarmManifest.for_net(net)
        assert m.matches(net)
        assert not m.matches(_mlp(hidden=32))
        stale = cc.WarmManifest(cc.model_fingerprint(net), "jax-0.0/other/?")
        assert not stale.matches(net)

    def test_attach_manifest_mismatch_raises(self):
        net = _mlp()
        with pytest.raises(ValueError, match="does not match"):
            cc.attach_manifest(net, cc.WarmManifest.for_net(_mlp(hidden=32)))

    def test_aot_compile_manifest_first_then_serialize_back(self):
        telemetry.enable()
        m = cc.WarmManifest("m")
        f = jax.jit(lambda x: x + 1.0)
        ex1, src1 = cc.aot_compile(f, jnp.ones(5), manifest=m, kind="t")
        assert src1 == "compile" and len(m) == 1
        ex2, src2 = cc.aot_compile(f, jnp.ones(5), manifest=m, kind="t")
        assert src2 == "manifest"
        np.testing.assert_allclose(np.asarray(ex2(jnp.ones(5))), 2.0)
        ev = cc.event_counts()
        assert ev.get("miss") == 1 and ev.get("serialize") == 1 \
            and ev.get("hit") == 1


# ---------------------------------------------------------------------------
# cold-start gauges
# ---------------------------------------------------------------------------

class TestFirstMarks:
    def test_note_first_step_stamps_once(self):
        telemetry.enable()
        ms = cc.note_first_step()
        assert ms is not None and ms > 0
        assert cc.note_first_step() is None  # once per process
        assert cc.first_marks()["step"] == ms

    def test_reset_marks_via_telemetry_reset(self):
        cc.note_first_step()
        cc.note_first_request()
        telemetry.reset()
        assert cc.first_marks() == {}

    def test_fit_stamps_time_to_first_step(self):
        telemetry.enable()
        x, y = _data()
        _mlp().fit(x, y, epochs=1, batch_size=16)
        assert cc.first_marks().get("step", 0) > 0

    def test_status_payload(self):
        telemetry.enable()
        cc.note_first_step()
        st = cc.status()
        assert set(st) >= {"persistent_cache_dir", "events",
                           "time_to_first_step_ms",
                           "time_to_first_request_ms"}
        assert st["time_to_first_step_ms"] > 0

    def test_health_payload_carries_compile_cache(self):
        from deeplearning4j_tpu.ui.server import _health_payload
        assert "compile_cache" in _health_payload()


# ---------------------------------------------------------------------------
# the one-zip resumable bundle + RNG chain (satellite)
# ---------------------------------------------------------------------------

class TestResumableUnit:
    def test_save_model_roundtrips_rng_chain(self, tmp_path):
        net = _mlp(dropout=0.3)
        x, y = _data()
        net.fit(x, y, epochs=1, batch_size=16)  # advances the chain
        p = save_model(net, str(tmp_path / "m.zip"))
        res = load_model(p)
        assert np.array_equal(np.asarray(res._rng), np.asarray(net._rng))

    def test_crash_resume_bit_exact_including_rng(self, tmp_path):
        # dropout ACTIVE: the resumed run must continue the key chain,
        # not replay it — params only match bit-exactly if it does
        x, y = _data(n=64)
        ref = _mlp(dropout=0.3)
        ref.fit(x, y, epochs=2, batch_size=16)       # uninterrupted
        net = _mlp(dropout=0.3)
        net.fit(x, y, epochs=1, batch_size=16)       # "crash" after epoch 1
        p = save_model(net, str(tmp_path / "ck.zip"))
        res = load_model(p)
        res.fit(x, y, epochs=1, batch_size=16)       # resume
        assert _leaves_equal(ref.params, res.params)
        assert _leaves_equal(ref.opt_state, res.opt_state)
        assert np.array_equal(np.asarray(ref._rng), np.asarray(res._rng))

    def test_bundle_folds_buckets_and_manifest(self, tmp_path):
        net = _mlp()
        cc.attach_manifest(net, cc.WarmManifest.for_net(net))
        x, y = _data()
        net.fit(x, y, epochs=1, batch_size=16, steps_per_dispatch=2)
        p = save_bundle(net, str(tmp_path / "b.zip"),
                        buckets=BucketRegistry([8, 16]))
        b = load_bundle(p)
        assert b.buckets.sizes() == [8, 16]
        assert len(b.manifest) == 1
        assert b.net._warm_manifest is b.manifest  # attached, ready to fit
        assert b.net.iteration == net.iteration
        assert _leaves_equal(net.params, b.net.params)

    def test_bundle_mismatched_manifest_dropped_with_warning(self, tmp_path):
        net = _mlp()
        other = _mlp(hidden=32)
        m = cc.WarmManifest.for_net(other)
        p = save_bundle(net, str(tmp_path / "b.zip"), manifest=m)
        # hand-corrupt: rewrite with a manifest claiming another model
        with zipfile.ZipFile(p) as z:
            names = z.namelist()
        assert "warm_manifest.zip" not in names  # empty manifest skipped
        m.put("k", "s", jax.jit(lambda v: v).lower(jnp.ones(3)).compile())  # graftlint: disable=R3 -- forging a mismatched manifest for the drop test
        p = save_bundle(net, str(tmp_path / "b2.zip"), manifest=m)
        with pytest.warns(UserWarning, match="manifest"):
            b = load_bundle(p)
        assert b.manifest is None
        assert getattr(b.net, "_warm_manifest", None) is None

    def test_plain_model_zip_loads_as_bundle(self, tmp_path):
        net = _mlp()
        p = save_model(net, str(tmp_path / "plain.zip"))
        b = load_bundle(p)
        assert b.buckets is None and b.manifest is None
        assert _leaves_equal(net.params, b.net.params)

    def test_corrupt_embedded_manifest_dropped_not_fatal(self, tmp_path):
        # a truncated warm_manifest.zip member must not take the
        # checkpoint down with it — the net restores, manifest is None
        net = _mlp()
        p = save_model(net, str(tmp_path / "b.zip"))
        with zipfile.ZipFile(p, "a") as z:
            z.writestr("warm_manifest.zip", b"\x00not a zip")
        with pytest.warns(UserWarning, match="corrupt"):
            b = load_bundle(p)
        assert b.manifest is None
        assert _leaves_equal(net.params, b.net.params)

    def test_sharded_trainer_bundle_extras(self, tmp_path):
        # the distributed tier's resumable unit: orbax sharded state +
        # bucket registry + warm manifest in one checkpoint directory
        from deeplearning4j_tpu.parallel.data_parallel import ParallelTrainer
        from deeplearning4j_tpu.utils.sharded_checkpoint import (
            restore_trainer, save_trainer)
        x, y = _data(n=32)
        tr = ParallelTrainer(_mlp())
        tr.init()
        tr.step(x[:16], y[:16])
        m = cc.WarmManifest.for_net(tr.net)
        m.put("k", "s", jax.jit(lambda v: v + 1).lower(jnp.ones(3)).compile())  # graftlint: disable=R3 -- forging a manifest entry for the extras round trip
        path = save_trainer(str(tmp_path / "ck"), tr,
                            buckets=BucketRegistry([16, 32]), manifest=m)
        tr2 = ParallelTrainer(_mlp())
        tr2.init()
        restore_trainer(path, tr2)
        assert tr2.iteration == tr.iteration
        assert tr2.buckets.sizes() == [16, 32]
        restored = getattr(tr2.net, "_warm_manifest", None)
        assert restored is not None and len(restored) == 1
        assert _leaves_equal(tr.params, tr2.params)


# ---------------------------------------------------------------------------
# warm restart: zero compiles (the acceptance claim)
# ---------------------------------------------------------------------------

class TestWarmRestartZeroCompiles:
    def test_fused_warm_restore_zero_compiles_bit_exact(self, tmp_path):
        telemetry.enable()
        x, y = _data(n=64)
        # uninterrupted twin (no manifest machinery at all)
        ref = _mlp(dropout=0.2)
        ref.fit(x, y, epochs=2, batch_size=16, steps_per_dispatch=2)
        # cold leg: manifest attached, fit, save the one resumable unit
        net = _mlp(dropout=0.2)
        cc.attach_manifest(net, cc.WarmManifest.for_net(net))
        net.fit(x, y, epochs=1, batch_size=16, steps_per_dispatch=2)
        p = save_bundle(net, str(tmp_path / "bundle.zip"))
        # warm leg: fresh net restored from the bundle
        telemetry.reset()
        telemetry.enable()
        b = load_bundle(p)
        b.net.fit(x, y, epochs=1, batch_size=16, steps_per_dispatch=2)
        # zero compiles: manifest hit counted, no miss, the fused
        # engine's inner jit cache never filled, recompiles_total flat
        ev = cc.event_counts()
        assert ev.get("hit", 0) > 0
        assert not ev.get("miss") and not ev.get("deserialize_fail")
        fns = list(b.net._train_steps_fused.values())
        assert fns and all(fn._cache_size() == 0 for fn, _m in fns)
        assert _counter_total("recompiles_total") == 0
        # and the warm continuation is bit-exact vs the uninterrupted run
        assert _leaves_equal(ref.params, b.net.params)
        assert np.array_equal(np.asarray(ref._rng), np.asarray(b.net._rng))

    def test_serving_warm_restore_zero_compiles(self, tmp_path):
        telemetry.enable()
        x, _ = _data(n=8, n_in=8)
        net = _mlp()
        cold = ServingEngine(net, name="wrm", input_spec=(8,),
                             buckets=[1, 4], warmup=True)
        direct = cold.output(x[:3])
        wm = cold.save_warm_manifest(str(tmp_path / "wm.zip"))
        assert wm is not None
        # fresh engine, fresh telemetry = the restarted process
        telemetry.reset()
        telemetry.enable()
        warm = ServingEngine(_mlp(), name="wrm2", input_spec=(8,),
                             buckets=[1, 4], warm_manifest=wm, warmup=True)
        st = warm.stats()["aot"]
        assert st["manifest"] == "attached"
        assert st["manifest_hits"] == st["warmed"] == 2
        assert st["manifest_misses"] == 0 and st["lazy_compiles"] == 0
        assert cc.event_counts().get("hit", 0) == 2
        # ZERO compiles on the warm path: neither the compile counter nor
        # the recompile counter moved for this site
        assert _counter_total("compiles_total", site="serving:wrm2") == 0
        assert _counter_total("recompiles_total", site="serving:wrm2") == 0
        # and the deserialized executables serve the same numbers
        np.testing.assert_allclose(np.asarray(warm.output(x[:3])),
                                   np.asarray(direct), rtol=1e-6)

    def test_serving_corrupt_manifest_file_degrades_to_cold(self, tmp_path):
        # a truncated/non-zip --warm-manifest file must not crash engine
        # construction — it degrades to a counted cold warmup
        bad = tmp_path / "wm.zip"
        bad.write_bytes(b"\x00definitely not a zip")
        with pytest.warns(UserWarning, match="unreadable"):
            eng = ServingEngine(_mlp(), name="crpt", input_spec=(8,),
                                buckets=[1], warm_manifest=str(bad),
                                warmup=True)
        st = eng.stats()["aot"]
        assert st["manifest"] == "none"
        assert st["warmed"] == 1 and st["manifest_hits"] == 0

    def test_serving_manifest_mismatch_refused(self, tmp_path):
        net = _mlp()
        cold = ServingEngine(net, name="mm", input_spec=(8,), buckets=[1],
                             warmup=True)
        wm = cold.save_warm_manifest(str(tmp_path / "wm.zip"))
        other = _mlp(hidden=32)
        eng = ServingEngine(other, name="mm2", input_spec=(8,),
                            buckets=[1], warm_manifest=wm, warmup=True)
        st = eng.stats()["aot"]
        assert st["manifest"] == "mismatch"
        assert st["manifest_hits"] == 0 and st["warmed"] == 1

    def test_serve_cli_warm_manifest_roundtrip(self, tmp_path, capsys):
        from deeplearning4j_tpu.cli import main
        net = _mlp(n_in=6)
        mp = str(tmp_path / "model.zip")
        save_model(net, mp)
        wm = str(tmp_path / "wm.zip")
        args = ["serve", "--model-path", mp, "--max-batch", "4",
                "--buckets", "1,4", "--port", "0", "--smoke", "2",
                "--warm-manifest", wm]
        assert main(list(args)) == 0
        assert os.path.exists(wm)
        capsys.readouterr()
        telemetry.reset()
        assert main(list(args)) == 0  # warm leg
        out = capsys.readouterr().out
        assert "2 from warm manifest, 0 compiled" in out
