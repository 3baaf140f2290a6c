"""Kernel autotuner tests (deeplearning4j_tpu/tuning, ISSUE 11).

Covers the tentpole mechanics end to end on CPU: config-space pruning
(VMEM budget, the TPU (8,128) tile rule, redundant clamps, divisibility),
TuningDB round-trip / corrupt / version-mismatch degradation, the parity
gate actually rejecting a wrong candidate, the runtime dispatch seams
consulting the DB (attention blocks + crossover, conv tiles, lstm column
tiles — hit/miss counter-observed), the warm-restart composition
(populated DB + warm manifest -> tuned executable, zero compiles,
hit-only counters, and a DB refresh invalidating stale manifest
entries), and the ``tune`` CLI smoke in interpret mode.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import telemetry, tuning
from deeplearning4j_tpu.ops import attention_pallas as ap
from deeplearning4j_tpu.ops import conv_pallas as cp
from deeplearning4j_tpu.ops import lstm_pallas as lp
from deeplearning4j_tpu.tuning import db as tdb
from deeplearning4j_tpu.tuning import measure as tmeasure
from deeplearning4j_tpu.tuning import tune as ttune
from deeplearning4j_tpu.utils import compile_cache as cc

F32 = jnp.float32


@pytest.fixture(autouse=True)
def _isolate(monkeypatch):
    monkeypatch.delenv(tuning.ENV_DB, raising=False)
    telemetry.reset()
    tuning.set_db(None)
    yield
    tuning.set_db(None)
    telemetry.reset()
    telemetry.disable()


def _events():
    return tuning.event_counts()


# ---------------------------------------------------------------------------
# config space: static validity pruning
# ---------------------------------------------------------------------------

class TestSpace:
    def test_enumerate_collapses_remat_without_grad(self):
        fwd = tuning.enumerate_space("attention")
        assert all(not c["remat"] for c in fwd)
        both = tuning.enumerate_space("attention", include_remat=True)
        assert len(both) == 2 * len(fwd)

    def test_tile_rule_rejects_non_lane_multiples(self):
        shape = (2, 4096, 4, 128)
        r = tuning.validate("attention", {"block_q": 64, "block_k": 128,
                                          "remat": False}, shape, F32)
        assert r and "tile rule" in r
        r = tuning.validate("conv_matmul", {"bn": 100, "bk": 128,
                                            "bj": 128},
                            (4096, 256, 256), F32)
        assert r and "8-multiple" in r
        r = tuning.validate("conv_matmul", {"bn": 128, "bk": 100,
                                            "bj": 128},
                            (4096, 256, 256), F32)
        assert r and "128-multiple" in r

    def test_vmem_budget_rejects(self):
        # 4096-row f32 q, k, v and out blocks at 128 lanes, double-buffered,
        # are 16 MiB before any scratch — over the budget
        r = tuning.validate("attention", {"block_q": 4096, "block_k": 4096,
                                          "remat": False},
                            (2, 8192, 4, 128), F32)
        assert r and "vmem" in r

    def test_redundant_clamp_rejects(self):
        # blocks past the 128-rounded sequence clamp to it — duplicates
        r = tuning.validate("attention", {"block_q": 512, "block_k": 512,
                                          "remat": False},
                            (2, 256, 4, 64), F32)
        assert r and "redundant" in r

    def test_lstm_divisibility(self):
        # hp=640 -> 4H=2560: 1024 does not divide, 256 does
        assert tuning.validate("lstm", {"tile_cols": 1024},
                               (8, 8, 640), F32)
        assert tuning.validate("lstm", {"tile_cols": 256},
                               (8, 8, 640), F32) is None

    def test_prune_splits(self):
        cands = [{"block_q": 64, "block_k": 128, "remat": False},
                 {"block_q": 128, "block_k": 128, "remat": False}]
        valid, rejected = tuning.prune("attention", cands,
                                       (1, 1024, 2, 64), F32)
        assert valid == [cands[1]]
        assert rejected[0][0] == cands[0] and "tile rule" in rejected[0][1]


# ---------------------------------------------------------------------------
# TuningDB: round-trip, degradation, counters
# ---------------------------------------------------------------------------

class TestDB:
    def test_bucket_shape_pow2(self):
        assert tuning.bucket_shape((1, 1000, 3, 64)) == (1, 1024, 4, 64)

    def test_record_lookup_counters(self):
        telemetry.enable()
        db = tuning.TuningDB()
        db.record("attention", (1, 256, 2, 32),
                  F32, {"block_q": 128, "block_k": 128})
        assert _events().get("tune") == 1
        # same bucket (T=200 -> 256) hits; another bucket misses
        assert db.lookup("attention", (1, 200, 2, 32), F32) == {
            "block_q": 128, "block_k": 128}
        assert _events().get("hit") == 1
        assert db.lookup("attention", (1, 4096, 2, 32), F32) is None
        assert _events().get("miss") == 1

    def test_save_load_roundtrip(self, tmp_path):
        db = tuning.TuningDB()
        db.record("conv_matmul", (256, 128, 128), F32,
                  {"bn": 128, "bk": 128, "bj": 128}, score_ms=1.5)
        p = str(tmp_path / "db.json")
        db.save(p)
        db2 = tuning.TuningDB.load(p)
        assert db2.entries == db.entries
        assert db2.lookup("conv_matmul", (256, 128, 128), F32)["bn"] == 128

    def test_corrupt_file_degrades_counted(self, tmp_path):
        telemetry.enable()
        p = tmp_path / "bad.json"
        p.write_text("{ not json !!")
        with pytest.warns(UserWarning, match="unusable"):
            assert tuning.TuningDB.load_lenient(str(p)) is None
        assert _events().get("mismatch_drop") == 1

    def test_version_mismatch_degrades_counted(self, tmp_path):
        telemetry.enable()
        p = tmp_path / "future.json"
        p.write_text(json.dumps({"tuning_db_version": 99, "entries": {}}))
        with pytest.warns(UserWarning, match="newer"):
            assert tuning.TuningDB.load_lenient(str(p)) is None
        assert _events().get("mismatch_drop") == 1

    def test_missing_file_silent(self, tmp_path):
        telemetry.enable()
        assert tuning.TuningDB.load_lenient(
            str(tmp_path / "absent.json")) is None
        assert not _events().get("mismatch_drop")

    def test_backend_mismatch_misses(self):
        telemetry.enable()
        db = tuning.TuningDB()
        # an entry recorded on another backend: key never matches here
        db.entries["attention|1,256,2,32|float32|jax-0.0.0/tpu/v5e"] = {
            "config": {"block_q": 128, "block_k": 128}}
        assert db.lookup("attention", (1, 256, 2, 32), F32) is None
        assert _events().get("miss") == 1

    def test_env_resolution_and_explicit_override(self, tmp_path,
                                                  monkeypatch):
        db = tuning.TuningDB()
        db.record("attention", (1, 256, 2, 32), F32,
                  {"block_q": 256, "block_k": 128})
        p = str(tmp_path / "env.json")
        db.save(p)
        monkeypatch.setenv(tuning.ENV_DB, p)
        cfg = tuning.tuned_config("attention", (1, 256, 2, 32), F32)
        assert cfg == {"block_q": 256, "block_k": 128}
        # explicit binding wins over the env artifact
        other = tuning.TuningDB()
        tuning.set_db(other)
        assert tuning.tuned_config("attention", (1, 256, 2, 32),
                                   F32) is None
        tuning.set_db(None)  # back to env resolution
        assert tuning.tuned_config("attention", (1, 256, 2, 32),
                                   F32) == cfg

    def test_fingerprint_tracks_content(self):
        db = tuning.TuningDB()
        db.record("attention", (1, 256, 2, 32), F32, {"block_q": 128})
        f1 = db.fingerprint()
        db.record("attention", (1, 256, 2, 32), F32, {"block_q": 256})
        assert db.fingerprint() != f1


# ---------------------------------------------------------------------------
# measurement harness: parity gate + chained timing
# ---------------------------------------------------------------------------

class TestMeasure:
    def test_parity_diff_trees_and_poison(self):
        a = (jnp.ones((2, 2)), jnp.zeros((3,)))
        b = (jnp.ones((2, 2)), jnp.zeros((3,)))
        assert tuning.parity_diff(a, b) == 0.0
        c = (jnp.ones((2, 2)) * 1.5, jnp.zeros((3,)))
        assert tuning.parity_diff(a, c) == pytest.approx(0.5)
        assert tuning.parity_diff(a, jnp.ones((2, 2))) == float("inf")
        nan = (jnp.full((2, 2), np.nan), jnp.zeros((3,)))
        assert tuning.parity_diff(nan, b) == float("inf")

    def test_time_callable_runs(self):
        x = jnp.arange(8.0, dtype=F32)
        dt = tuning.time_callable(lambda x: x * 2.0, (x,), iters=3,
                                  reps=1)
        assert dt > 0 and np.isfinite(dt)

    def test_parity_rejection_rejects_wrong_candidate(self):
        telemetry.enable()
        x = jnp.arange(16.0, dtype=F32)

        def build(cfg):
            scale = 1.001 if cfg["bug"] else 1.0
            return lambda x: x * (2.0 * scale)

        winner, results = tuning.search(
            "demo", [{"bug": True}, {"bug": False}], build, (x,),
            lambda x: x * 2.0, iters=2, reps=1)
        assert winner is not None and winner.config == {"bug": False}
        rejected = [m for m in results if not m.ok]
        assert len(rejected) == 1 and rejected[0].config == {"bug": True}
        assert "parity" in rejected[0].rejected
        assert _events().get("reject") == 1

    def test_search_all_rejected_returns_none(self):
        telemetry.enable()
        x = jnp.arange(4.0, dtype=F32)
        winner, results = tuning.search(
            "demo", [{"bug": True}], lambda c: (lambda x: x + 1.0), (x,),
            lambda x: x * 2.0, iters=1, reps=1)
        assert winner is None and not results[0].ok
        assert _events().get("reject") == 1

    def test_rejected_candidate_never_persisted(self, tmp_path):
        """The bench gate's invariant at unit level: tune events == DB
        entries even when candidates reject."""
        telemetry.enable()
        db = tuning.TuningDB()
        x = jnp.arange(16.0, dtype=F32)

        def build(cfg):
            scale = 1.001 if cfg["bug"] else 1.0
            return lambda x: x * (2.0 * scale)

        winner, _ = tuning.search(
            "demo", [{"bug": True}, {"bug": False}], build, (x,),
            lambda x: x * 2.0, iters=2, reps=1)
        db.record("demo", (16,), F32, winner.config)
        assert _events().get("tune") == 1 == len(db)


# ---------------------------------------------------------------------------
# runtime dispatch: the ops seams consult the DB
# ---------------------------------------------------------------------------

class TestRuntimeDispatch:
    def _db_with_attention(self, shape=(1, 256, 2, 32), **cfg):
        db = tuning.TuningDB()
        db.record("attention", shape, F32, cfg or
                  {"backend": "flash", "block_q": 256, "block_k": 256})
        tuning.set_db(db)
        return db

    def test_resolve_priority_db_env_default(self, monkeypatch):
        shape = (1, 256, 2, 32)
        # default table
        assert ap.resolve_block_sizes(shape, F32) == (512, 512, False)
        # env override (validated: junk falls back)
        monkeypatch.setenv("DL4J_TPU_FLASH_BLOCK_Q", "256")
        monkeypatch.setenv("DL4J_TPU_FLASH_BLOCK_K", "100")
        assert ap.resolve_block_sizes(shape, F32) == (256, 512, False)
        # DB wins over env
        self._db_with_attention(shape, backend="flash", block_q=128,
                                block_k=128, remat=True)
        assert ap.resolve_block_sizes(shape, F32) == (128, 128, True)

    def test_supported_crossover_consults_db(self):
        long = (1, 2048, 2, 32)
        short = (1, 256, 2, 32)
        # no DB: the hand-measured min-seq heuristic
        assert ap.supported(long, long, None, F32)
        assert not ap.supported(short, short, None, F32)
        # DB verdicts override it in BOTH directions
        db = tuning.TuningDB()
        db.record("attention", long, F32, {"backend": "xla"})
        db.record("attention", short, F32,
                  {"backend": "flash", "block_q": 128, "block_k": 128})
        tuning.set_db(db)
        assert not ap.supported(long, long, None, F32)
        assert ap.supported(short, short, None, F32)

    def test_flash_attention_uses_tuned_blocks(self, monkeypatch):
        self._db_with_attention()
        calls = []
        orig = ap._run_fwd

        def spy(q, k, v, mask, h, causal, scale, bq, bk, interp):
            calls.append((bq, bk))
            return orig(q, k, v, mask, h, causal, scale, bq, bk, interp)

        monkeypatch.setattr(ap, "_run_fwd", spy)
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.normal(size=(1, 256, 2, 32)) * 0.1, F32)
        out = ap.flash_attention(q, q, q, interpret=True)
        assert calls == [(256, 256)] and out.shape == q.shape
        # explicit blocks still win unconditionally (tests, the tuner)
        calls.clear()
        ap.flash_attention(q, q, q, block_q=128, block_k=128,
                           interpret=True)
        assert calls == [(128, 128)]

    def test_flash_attention_block_routes_through_table(self, monkeypatch):
        """The ring-attention entry used to hardcode 512x512 and bypass
        even the env override; it now resolves through the same table."""
        calls = []
        orig = ap._run_fwd

        def spy(q, k, v, mask, h, causal, scale, bq, bk, interp):
            calls.append((bq, bk))
            return orig(q, k, v, mask, h, causal, scale, bq, bk, interp)

        monkeypatch.setattr(ap, "_run_fwd", spy)
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.normal(size=(1, 128, 2, 16)) * 0.1, F32)
        monkeypatch.setenv("DL4J_TPU_FLASH_BLOCK_Q", "256")
        monkeypatch.setenv("DL4J_TPU_FLASH_BLOCK_K", "256")
        ap.flash_attention_block(q, q, q, False, 0.25, True)
        assert calls == [(256, 256)]
        calls.clear()
        self._db_with_attention((1, 128, 2, 16), backend="flash",
                                block_q=128, block_k=128)
        out, lse = ap.flash_attention_block(q, q, q, False, 0.25, True)
        assert calls == [(128, 128)]
        assert out.shape == q.shape and lse.shape == (1, 2, 128)

    def test_flash_attention_block_grad_uses_resolved_blocks(self):
        """fwd/bwd parity under a tuned block size (bk rides the
        residuals into _run_bwd)."""
        self._db_with_attention((1, 128, 2, 16), backend="flash",
                                block_q=128, block_k=128)
        rs = np.random.RandomState(1)
        q, k, v = (jnp.asarray(rs.normal(size=(1, 128, 2, 16)) * 0.1, F32)
                   for _ in range(3))

        def loss_blk(q, k, v):
            o, _ = ap.flash_attention_block(q, k, v, False, 0.25, True)
            return jnp.sum(o * o)

        def loss_ref(q, k, v):
            o = ttune.naive_attention(q, k, v)
            return jnp.sum(o * o)

        g_blk = jax.grad(loss_blk)(q, k, v)
        # naive_attention uses 1/sqrt(d)=0.25 for d=16: same scale
        g_ref = jax.grad(loss_ref)(q, k, v)
        assert float(jnp.max(jnp.abs(g_blk - g_ref))) < 1e-5

    def test_conv_matmul_consults_db_counted(self):
        telemetry.enable()
        db = tuning.TuningDB()
        db.record("conv_matmul", (64, 32, 64), F32,
                  {"bn": 128, "bk": 128, "bj": 128})
        tuning.set_db(db)
        before = _events().get("hit", 0)
        rs = np.random.RandomState(2)
        x = jnp.asarray(rs.normal(size=(64, 32)) * 0.1, F32)
        w = jnp.asarray(rs.normal(size=(32, 64)) * 0.1, F32)
        z, stats = cp._matmul_stats(x, w, True)
        assert _events().get("hit", 0) == before + 1
        ref = jnp.dot(x, w)
        assert float(jnp.max(jnp.abs(z - ref))) < 1e-6
        # explicit blocks (the tuner's candidates) skip the DB
        before = _events().get("hit", 0)
        cp._matmul_stats(x, w, True, bn=128, bk=128, bj=128)
        assert _events().get("hit", 0) == before

    def test_lstm_tile_override_and_db(self):
        telemetry.enable()
        t, b, hidden = 3, 2, 640  # hp 640 > 512: the tiled kernel
        rs = np.random.RandomState(3)
        xz = jnp.asarray(rs.normal(size=(t, b, 4 * hidden)) * 0.1, F32)
        wh = jnp.asarray(rs.normal(size=(hidden, 4 * hidden)) * 0.1, F32)
        h0 = jnp.zeros((b, hidden), F32)
        c0 = jnp.zeros((b, hidden), F32)
        ref = ttune._ref_lstm(xz, wh, h0, c0)
        # explicit tile_cols
        out = lp.fused_sequence_padded(xz, wh, h0, c0, interpret=True,
                                       tile_cols=256)
        assert tuning.parity_diff(out, ref) < 1e-6
        # DB-driven tile_cols (counted), including fallback on an
        # invalid stale value
        db = tuning.TuningDB()
        db.record("lstm", (t, b, hidden), F32, {"tile_cols": 512})
        tuning.set_db(db)
        before = _events().get("hit", 0)
        out2 = lp.fused_sequence_padded(xz, wh, h0, c0, interpret=True)
        assert _events().get("hit", 0) == before + 1
        assert tuning.parity_diff(out2, ref) < 1e-6
        db.record("lstm", (t, b, hidden), F32, {"tile_cols": 999})
        out3 = lp.fused_sequence_padded(xz, wh, h0, c0, interpret=True)
        assert tuning.parity_diff(out3, ref) < 1e-6  # fell back, no crash


# ---------------------------------------------------------------------------
# warm-restart composition: DB + manifest -> tuned executables for free
# ---------------------------------------------------------------------------

class TestWarmRestart:
    def test_full_signature_passthrough_without_db(self):
        assert cc.full_signature("sig") == "sig"
        db = tuning.TuningDB()
        tuning.set_db(db)  # bound but EMPTY: still a passthrough
        assert cc.full_signature("sig") == "sig"
        db.record("attention", (1, 256, 2, 32), F32, {"block_q": 128})
        assert cc.full_signature("sig") == f"sig|tuning:{db.fingerprint()}"

    def test_warm_restart_tuned_zero_compiles_counter_asserted(self):
        telemetry.enable()
        db = tuning.TuningDB()
        db.record("attention", (1, 256, 2, 32), F32,
                  {"backend": "flash", "block_q": 256, "block_k": 256})
        tuning.set_db(db)
        rs = np.random.RandomState(4)
        q = jnp.asarray(rs.normal(size=(1, 256, 2, 32)) * 0.1, F32)

        def fn(q, k, v):
            return ap.flash_attention(q, k, v, interpret=True)

        man = cc.WarmManifest(model_fp="test:tuning")
        ex, src = cc.aot_compile(jax.jit(fn), q, q, q, manifest=man,
                                 kind="test:tuning")
        assert src == "compile"
        out_cold = np.asarray(ex(q, q, q))
        blob = man.to_bytes()

        # --- simulated restart: fresh manifest object + fresh jit; the
        # dispatch consults the DB (hit) and the executable loads FROM
        # the manifest (hit, zero compiles)
        man2 = cc.WarmManifest.from_bytes(blob)
        cc0, tu0 = dict(cc.event_counts()), dict(_events())
        assert tuning.tuned_config("attention", (1, 256, 2, 32),
                                   F32)["block_q"] == 256
        ex2, src2 = cc.aot_compile(jax.jit(fn), q, q, q, manifest=man2,
                                   kind="test:tuning")
        assert src2 == "manifest"
        cc1, tu1 = cc.event_counts(), _events()
        assert cc1.get("hit", 0) - cc0.get("hit", 0) == 1
        assert cc1.get("miss", 0) == cc0.get("miss", 0)
        assert cc1.get("serialize", 0) == cc0.get("serialize", 0)
        assert tu1.get("hit", 0) - tu0.get("hit", 0) == 1
        assert tu1.get("miss", 0) == tu0.get("miss", 0)
        out_warm = np.asarray(ex2(q, q, q))
        np.testing.assert_array_equal(out_cold, out_warm)

    def test_db_refresh_invalidates_stale_manifest(self):
        telemetry.enable()
        db = tuning.TuningDB()
        db.record("attention", (1, 256, 2, 32), F32,
                  {"backend": "flash", "block_q": 256, "block_k": 256})
        tuning.set_db(db)
        rs = np.random.RandomState(5)
        q = jnp.asarray(rs.normal(size=(1, 256, 2, 32)) * 0.1, F32)

        def fn(q):
            return ap.flash_attention(q, q, q, interpret=True)

        man = cc.WarmManifest(model_fp="test:tuning")
        _, src = cc.aot_compile(jax.jit(fn), q, manifest=man,
                                kind="test:tuning")
        assert src == "compile"
        # a re-tune changes the DB content -> the manifest key no longer
        # matches: the stale executable (baked with the OLD blocks) must
        # MISS, not silently serve
        db.record("attention", (1, 256, 2, 32), F32,
                  {"backend": "flash", "block_q": 128, "block_k": 128})
        _, src2 = cc.aot_compile(jax.jit(fn), q, manifest=man,
                                 kind="test:tuning")
        assert src2 == "compile"


# ---------------------------------------------------------------------------
# tune drivers + CLI smoke (CPU interpret mode)
# ---------------------------------------------------------------------------

class TestTuneDrivers:
    def test_tune_attention_records_winner(self):
        telemetry.enable()
        db = tuning.TuningDB()
        s = ttune.tune_attention(
            db, b=1, t=128, h=2, d=16, interpret=True, iters=2, reps=1,
            include_xla=False,
            candidates=[{"block_q": 128, "block_k": 128, "remat": False}])
        assert s["winner"] == {"block_q": 128, "block_k": 128,
                               "remat": False}
        assert s["rejected_parity"] == 0 and len(db) == 1
        cfg = db.lookup("attention", (1, 128, 2, 16), F32)
        assert cfg["backend"] == "flash" and cfg["block_q"] == 128

    @pytest.mark.parametrize("xla_ms,flash_ms,backend", [
        (1.0, 5.0, "xla"),      # the naive path is the faster one here
        (5.0, 1.0, "flash"),    # the kernel is
    ])
    def test_tune_attention_crossover_records_the_faster_backend(
            self, monkeypatch, xla_ms, flash_ms, backend):
        """The crossover candidate ("do not run the kernel") against one
        block geometry, with the two timings handed in: a CPU stopwatch
        says nothing about the chip, and the verdict must follow the
        timings whichever way they fall. The DB's verdict then routes the
        dispatch at a T below the _MIN_SEQ constant."""
        def timed(fn, args, **_):
            return 1e-3 * (xla_ms if fn is ttune.naive_attention
                           else flash_ms)
        monkeypatch.setattr(tmeasure, "time_callable", timed)
        db = tuning.TuningDB()
        s = ttune.tune_attention(
            db, b=1, t=128, h=2, d=16, interpret=True, iters=2, reps=1,
            candidates=[{"block_q": 128, "block_k": 128, "remat": False}])
        assert s["winner"].get("backend", "flash") == backend
        assert s["rejected_parity"] == 0
        tuning.set_db(db)
        shape = (1, 128, 2, 16)
        assert ap.supported(shape, shape, None, F32) == (backend == "flash")

    def test_tune_conv_matmul_smoke(self):
        db = tuning.TuningDB()
        s = ttune.tune_conv_matmul(
            db, n=64, cin=32, cout=64, interpret=True, iters=2, reps=1,
            candidates=[{"bn": 64, "bk": 128, "bj": 128}])
        assert s["winner"] == {"bn": 64, "bk": 128, "bj": 128}
        assert len(db) == 1


class TestCLI:
    def test_tune_cli_smoke(self, tmp_path, capsys):
        from deeplearning4j_tpu.cli import main
        p = str(tmp_path / "tuned.json")
        rc = main(["tune", "--db", p, "--kernels", "attention",
                   "--smoke"])
        assert rc == 0
        doc = json.loads(open(p).read())
        assert doc["tuning_db_version"] == 1
        assert len(doc["entries"]) == 1
        out = capsys.readouterr().out
        assert "winner" in out and "tuning DB" in out

    def test_tune_cli_requires_db(self, monkeypatch):
        from deeplearning4j_tpu.cli import main
        monkeypatch.delenv(tuning.ENV_DB, raising=False)
        with pytest.raises(SystemExit, match="no DB path"):
            main(["tune", "--smoke"])

    def test_tune_cli_merges_existing(self, tmp_path):
        from deeplearning4j_tpu.cli import main
        p = str(tmp_path / "tuned.json")
        assert main(["tune", "--db", p, "--kernels", "attention",
                     "--smoke"]) == 0
        assert main(["tune", "--db", p, "--kernels", "conv_matmul",
                     "--smoke"]) == 0
        doc = json.loads(open(p).read())
        kinds = {e["kernel"] for e in doc["entries"].values()}
        assert kinds == {"attention", "conv_matmul"}
