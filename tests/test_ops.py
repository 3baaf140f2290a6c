"""Custom-kernel tier tests (reference analog: CuDNNGradientChecks /
ValidateCudnnLSTM — fast path vs reference path on identical inputs,
SURVEY.md §4.6). Pallas kernels run in interpret mode on the CPU fixture."""

import ast
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import attention_pallas, lstm_pallas

PKG = pathlib.Path(attention_pallas.__file__).resolve().parents[1]

#: ROADMAP's third aim as a test: a choice between kernels, or between a
#: layer's paths, is made from what the code can observe and never from
#: the environment. These are the modules that make such choices.
_NO_ENVIRONMENT = sorted(p for pattern in ("ops/*.py", "nn/**/*.py",
                                           "models/*.py")
                         for p in PKG.glob(pattern))


@pytest.mark.parametrize("path", _NO_ENVIRONMENT,
                         ids=lambda p: str(p.relative_to(PKG)))
def test_no_kernel_or_layer_module_reads_the_environment(path):
    names = {"environ", "environb", "getenv", "getenvb"}
    reads = sorted({
        n.lineno for n in ast.walk(ast.parse(path.read_text()))
        if (isinstance(n, ast.Attribute) and n.attr in names)
        or (isinstance(n, ast.Name) and n.id in names)
        or (isinstance(n, ast.ImportFrom) and n.module == "os"
            and names & {a.name for a in n.names})})
    assert not reads, (f"{path.relative_to(PKG)} reads the environment at "
                       f"line(s) {reads}: choose from shape, dtype, mask or "
                       "jax.default_backend() instead")


def _ref_scan(xz, wh, h0, c0):
    def step(carry, xz_t):
        h, c = carry
        z = xz_t + h @ wh
        zi, zf, zg, zo = jnp.split(z, 4, -1)
        c = jax.nn.sigmoid(zf) * c + jax.nn.sigmoid(zi) * jnp.tanh(zg)
        h = jax.nn.sigmoid(zo) * jnp.tanh(c)
        return (h, c), h
    (hT, cT), hs = jax.lax.scan(step, (h0, c0), xz)
    return hs, (hT, cT)


def _ref_scan_peephole(xz, wh, wp, h0, c0):
    """GravesLSTM semantics: c_{t-1} peeps into i/f, c_t into o
    (LSTMHelpers.java:68 with hasPeepholeConnections)."""
    def step(carry, xz_t):
        h, c_prev = carry
        z = xz_t + h @ wh
        zi, zf, zg, zo = jnp.split(z, 4, -1)
        i = jax.nn.sigmoid(zi + wp[0] * c_prev)
        f = jax.nn.sigmoid(zf + wp[1] * c_prev)
        c = f * c_prev + i * jnp.tanh(zg)
        o = jax.nn.sigmoid(zo + wp[2] * c)
        h = o * jnp.tanh(c)
        return (h, c), h
    (hT, cT), hs = jax.lax.scan(step, (h0, c0), xz)
    return hs, (hT, cT)


class TestFusedPeepholeLstmKernel:
    def _inputs(self, T=3, B=8, H=128, seed=5):
        xz, wh, h0, c0 = _inputs(T=T, B=B, H=H, seed=seed)
        rs = np.random.RandomState(seed + 100)
        wp = jnp.asarray(rs.randn(3, H).astype(np.float32) * 0.1)
        return xz, wh, wp, h0, c0

    def test_forward_matches_scan(self):
        xz, wh, wp, h0, c0 = self._inputs()
        hs_p, (hT_p, cT_p) = lstm_pallas.lstm_fused_sequence_peephole(
            xz, wh, wp, h0, c0, True)
        hs_r, (hT_r, cT_r) = _ref_scan_peephole(xz, wh, wp, h0, c0)
        np.testing.assert_allclose(np.asarray(hs_p), np.asarray(hs_r),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(cT_p), np.asarray(cT_r),
                                   atol=1e-5)

    def test_gradients_match_scan(self):
        xz, wh, wp, h0, c0 = self._inputs(seed=6)

        def make_loss(fn):
            def loss(xz, wh, wp, h0, c0):
                hs, (hT, cT) = fn(xz, wh, wp, h0, c0)
                return (jnp.sum(hs ** 2) + jnp.sum(jnp.tanh(hT))
                        + 0.5 * jnp.sum(cT ** 2))
            return loss

        gp = jax.grad(make_loss(
            lambda *a: lstm_pallas.lstm_fused_sequence_peephole(*a, True)),
            argnums=(0, 1, 2, 3, 4))(xz, wh, wp, h0, c0)
        gr = jax.grad(make_loss(_ref_scan_peephole),
                      argnums=(0, 1, 2, 3, 4))(xz, wh, wp, h0, c0)
        for p, r, name in zip(gp, gr, ("dxz", "dwh", "dwp", "dh0", "dc0")):
            np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                       atol=2e-5, err_msg=name)

    def test_padded_peephole_matches_scan(self):
        xz, wh, wp, h0, c0 = self._inputs(H=100, seed=7)
        hs_p, (hT_p, cT_p) = lstm_pallas.fused_sequence_padded(
            xz, wh, h0, c0, wp=wp, interpret=True)
        hs_r, (hT_r, cT_r) = _ref_scan_peephole(xz, wh, wp, h0, c0)
        np.testing.assert_allclose(np.asarray(hs_p), np.asarray(hs_r),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(cT_p), np.asarray(cT_r),
                                   atol=1e-5)

    def test_matches_graveslstm_layer_semantics(self):
        """The kernel must agree with the GravesLSTM layer's scan path — the
        contract ValidateCudnnLSTM.java pins for the reference fast path."""
        from deeplearning4j_tpu.nn import layers as L
        from deeplearning4j_tpu.nn.conf import inputs as I

        layer = L.GravesLSTM(n_out=128)
        params = layer.init(jax.random.PRNGKey(0), I.RecurrentType(16, 4))
        rs = np.random.RandomState(8)
        x = jnp.asarray(rs.randn(8, 4, 16).astype(np.float32) * 0.5)
        y_scan, _ = layer.apply(params, {}, x)

        b, t, _ = x.shape
        xz = (x.reshape(b * t, -1) @ params["Wx"] + params["b"]) \
            .reshape(b, t, -1).transpose(1, 0, 2)
        h0 = jnp.zeros((b, 128), jnp.float32)
        c0 = jnp.zeros((b, 128), jnp.float32)
        hs, _ = lstm_pallas.lstm_fused_sequence_peephole(
            xz, params["Wh"], params["Wp"], h0, c0, True)
        np.testing.assert_allclose(np.asarray(hs.transpose(1, 0, 2)),
                                   np.asarray(y_scan), atol=1e-5)


def _inputs(T=4, B=8, H=128, seed=0):
    rs = np.random.RandomState(seed)
    xz = jnp.asarray(rs.randn(T, B, 4 * H).astype(np.float32) * 0.1)
    wh = jnp.asarray(rs.randn(H, 4 * H).astype(np.float32) * 0.1)
    h0 = jnp.asarray(rs.randn(B, H).astype(np.float32) * 0.1)
    c0 = jnp.asarray(rs.randn(B, H).astype(np.float32) * 0.1)
    return xz, wh, h0, c0


class TestFusedLstmKernel:
    def test_forward_matches_scan(self):
        xz, wh, h0, c0 = _inputs()
        hs_p, (hT_p, cT_p) = lstm_pallas.lstm_fused_sequence(xz, wh, h0, c0, True)
        hs_r, (hT_r, cT_r) = _ref_scan(xz, wh, h0, c0)
        np.testing.assert_allclose(np.asarray(hs_p), np.asarray(hs_r),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(cT_p), np.asarray(cT_r),
                                   atol=1e-5)

    def test_gradients_match_scan(self):
        xz, wh, h0, c0 = _inputs(T=3, B=8, H=128, seed=1)

        def make_loss(fn):
            def loss(xz, wh, h0, c0):
                hs, (hT, cT) = fn(xz, wh, h0, c0)
                return (jnp.sum(hs ** 2) + jnp.sum(jnp.tanh(hT))
                        + 0.5 * jnp.sum(cT ** 2))
            return loss

        gp = jax.grad(make_loss(
            lambda *a: lstm_pallas.lstm_fused_sequence(*a, True)),
            argnums=(0, 1, 2, 3))(xz, wh, h0, c0)
        gr = jax.grad(make_loss(_ref_scan), argnums=(0, 1, 2, 3))(xz, wh, h0, c0)
        for p, r, name in zip(gp, gr, ("dxz", "dwh", "dh0", "dc0")):
            np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                       atol=2e-5, err_msg=name)

    def test_nonzero_initial_state_threads_through(self):
        xz, wh, h0, c0 = _inputs(T=2, B=8, H=128, seed=2)
        hs, (hT, cT) = lstm_pallas.lstm_fused_sequence(xz, wh, h0, c0, True)
        # manually step twice
        hs_r, (hT_r, _) = _ref_scan(xz, wh, h0, c0)
        np.testing.assert_allclose(np.asarray(hT), np.asarray(hT_r), atol=1e-5)

    def test_supported_gating(self):
        ok = dict(peephole=False, mask=None, gate_activation="sigmoid",
                  activation="tanh")
        assert lstm_pallas.supported((8, 16, 32), 128, **ok)
        assert lstm_pallas.supported((8, 16, 32), 100, **ok)   # lane-padded
        assert not lstm_pallas.supported((8, 16, 32), 64, **ok)  # too small
        assert not lstm_pallas.supported((4, 16, 32), 128, **ok)  # B<8
        assert lstm_pallas.supported(
            (8, 16, 32), 128, **{**ok, "peephole": True})  # peephole kernel
        # [B, T] sequence masks ride the kernel (VERDICT r3 #4); other
        # mask ranks fall back
        assert lstm_pallas.supported(
            (8, 16, 32), 128, **{**ok, "mask": np.ones((8, 16))})
        assert not lstm_pallas.supported(
            (8, 16, 32), 128, **{**ok, "mask": np.ones((8, 16, 1))})
        assert not lstm_pallas.supported(
            (8, 16, 32), 128, **{**ok, "activation": "relu"})
        # H>512 now dispatches to the tiled-Wh kernel (TestTiledLstmKernel);
        # resident-kernel boundary stays at 512
        assert lstm_pallas.supported((8, 16, 32), 1024, **ok)
        assert lstm_pallas.supported((8, 16, 32), 512, **ok)

    def test_padded_dispatch_matches_unpadded_exactly(self):
        # H=100 -> padded to 128; padding is exact (zero lanes stay zero)
        xz, wh, h0, c0 = _inputs(T=3, B=8, H=100, seed=3)
        hs_p, (hT_p, cT_p) = lstm_pallas.fused_sequence_padded(
            xz, wh, h0, c0, interpret=True)
        hs_r, (hT_r, cT_r) = _ref_scan(xz, wh, h0, c0)
        np.testing.assert_allclose(np.asarray(hs_p), np.asarray(hs_r),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(cT_p), np.asarray(cT_r),
                                   atol=1e-5)

    def test_padded_gradients_match_scan(self):
        xz, wh, h0, c0 = _inputs(T=3, B=8, H=100, seed=4)

        def make_loss(fn):
            def loss(xz, wh, h0, c0):
                hs, (hT, cT) = fn(xz, wh, h0, c0)
                return jnp.sum(hs ** 2) + jnp.sum(jnp.tanh(hT)) + jnp.sum(cT ** 2)
            return loss

        gp = jax.grad(make_loss(lambda *a: lstm_pallas.fused_sequence_padded(
            *a, interpret=True)), argnums=(0, 1, 2, 3))(xz, wh, h0, c0)
        gr = jax.grad(make_loss(_ref_scan), argnums=(0, 1, 2, 3))(xz, wh, h0, c0)
        for p, r, name in zip(gp, gr, ("dxz", "dwh", "dh0", "dc0")):
            np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                       atol=2e-5, err_msg=name)

    def test_layer_never_dispatches_fused_on_cpu(self):
        # dispatch seam: CPU backend must stay on the scan path
        from deeplearning4j_tpu.nn import layers as L
        layer = L.LSTM(n_out=128)
        x = jnp.zeros((8, 4, 16))
        assert not layer._fused_eligible(x, None)


def _pallas_calls(jaxpr):
    """Every pallas_call equation under ``jaxpr``, through custom_vjp and
    pjit bodies."""
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            yield e
        for v in e.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    yield from _pallas_calls(inner)


class TestLstmDispatchTables:
    """The LSTM seam's answers as tables: ``supported()`` by hidden size,
    batch and peepholes, and the kernel the call then builds (resident
    under 512, else tiled) with its Wh column tile (ISSUE 29)."""

    OK = dict(mask=None, gate_activation="sigmoid", activation="tanh")

    @pytest.mark.parametrize("peephole", [False, True],
                             ids=["plain", "peephole"])
    @pytest.mark.parametrize("hsz,by_batch", [
        (96, {4: False, 8: True, 256: True}),    # lane-padded to 128
        (512, {4: False, 8: True, 256: True}),   # the resident bound
        (640, {4: False, 8: True, 256: True}),
        (1024, {4: False, 8: True, 256: True}),
        (2048, {4: False, 8: True, 256: False}),  # past the VMEM estimate
    ])
    def test_supported(self, hsz, by_batch, peephole):
        for b, want in by_batch.items():
            assert lstm_pallas.supported(
                (b, 16, 32), hsz, peephole=peephole, **self.OK) == want, b

    @pytest.mark.parametrize("peephole", [False, True],
                             ids=["plain", "peephole"])
    @pytest.mark.parametrize("hsz,grid,wh_block", [
        (96, (3,), (128, 512)),        # resident: Wh whole, padded lanes
        (512, (3,), (512, 2048)),
        (640, (3, 4), (640, 640)),     # tiled: 2560 has no 1024 divisor
        (1024, (3, 4), (1024, 1024)),
        (2048, (3, 8), (2048, 1024)),
    ])
    def test_kernel_and_tile_by_hidden_size(self, hsz, grid, wh_block,
                                            peephole):
        t, b = 3, 8
        S = jax.ShapeDtypeStruct
        f32 = jnp.float32
        args = (S((t, b, 4 * hsz), f32), S((hsz, 4 * hsz), f32),
                S((b, hsz), f32), S((b, hsz), f32))
        wp = S((3, hsz), f32) if peephole else None

        def call(xz, wh, h0, c0, wp=None):
            return lstm_pallas.fused_sequence_padded(
                xz, wh, h0, c0, wp=wp, interpret=True)
        jaxpr = (jax.make_jaxpr(call)(*args, wp) if peephole
                 else jax.make_jaxpr(call)(*args))
        (eqn,) = _pallas_calls(jaxpr.jaxpr)
        gm = eqn.params["grid_mapping"]
        assert gm.grid == grid
        assert tuple(getattr(d, "block_size", d) for d in
                     gm.block_mappings[1].block_shape) == wh_block

    def test_enabled_is_the_backend_gate(self, monkeypatch):
        assert not lstm_pallas.enabled()
        monkeypatch.setattr(attention_pallas, "backend_is_tpu", lambda: True)
        assert lstm_pallas.enabled()


class TestTiledLstmKernel:
    """Large-H variant (H > _RESIDENT_MAX_H streams Wh column tiles —
    VERDICT r2 #5, reference: CudnnLSTMHelper had no hidden-size cap).
    Interpret mode on CPU; small T/B keep it tractable."""

    def test_forward_matches_scan_h1024(self):
        xz, wh, h0, c0 = _inputs(T=2, B=8, H=1024, seed=11)
        hs_f, (hT_f, cT_f) = lstm_pallas.lstm_fused_sequence(
            xz, wh, h0, c0, True)
        hs_r, (hT_r, cT_r) = _ref_scan(xz, wh, h0, c0)
        np.testing.assert_allclose(np.asarray(hs_f), np.asarray(hs_r),
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(cT_f), np.asarray(cT_r),
                                   atol=1e-4)

    def test_tiled_kernel_actually_selected(self):
        # the dispatch boundary: resident path at 512, tiled above
        assert lstm_pallas._RESIDENT_MAX_H == 512
        assert lstm_pallas.supported((8, 4, 64), 1024, peephole=False,
                                     mask=None, gate_activation="sigmoid",
                                     activation="tanh")
        assert lstm_pallas.supported((8, 4, 64), 2048, peephole=False,
                                     mask=None, gate_activation="sigmoid",
                                     activation="tanh")
        # peephole rides the tiled kernel above the resident bound too
        # (VERDICT r3 #4 — CudnnLSTMHelper had no size split)
        assert lstm_pallas.supported((8, 4, 64), 1024, peephole=True,
                                     mask=None,
                                     gate_activation="sigmoid",
                                     activation="tanh")
        # VMEM gate: very large B x H combinations refuse
        assert not lstm_pallas.supported((512, 4, 64), 2048, peephole=False,
                                         mask=None,
                                         gate_activation="sigmoid",
                                         activation="tanh")

    def test_gradients_match_scan_h640(self):
        # H=640 > 512 exercises the tiled path with a non-tile-multiple 4H
        # (2560 -> tile 1024 doesn't divide): pad_hidden keeps H at 640
        # (128-multiple) and the runner clamps the tile to a divisor
        xz, wh, h0, c0 = _inputs(T=2, B=8, H=640, seed=12)

        def loss_fused(*a):
            hs, (hT, cT) = lstm_pallas.lstm_fused_sequence(*a, True)
            return (hs * hs).sum() + (hT * cT).sum()

        def loss_ref(*a):
            hs, (hT, cT) = _ref_scan(*a)
            return (hs * hs).sum() + (hT * cT).sum()

        gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(xz, wh, h0, c0)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(xz, wh, h0, c0)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)


class TestFlashAttention:
    """ops/attention_pallas.py vs the reference einsum attention
    (interpret mode on CPU; the dispatch itself is TPU-gated)."""

    def _ref(self, q, k, v, causal=False):
        import jax.numpy as jnp
        d = q.shape[-1]
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        if causal:
            t = logits.shape[-1]
            logits = jnp.where(jnp.tril(jnp.ones((t, t), bool)), logits,
                               -jnp.inf)
        w = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v)

    def _rand(self, b=2, t=24, h=2, d=8, seed=0):
        rs = np.random.RandomState(seed)
        mk = lambda: rs.randn(b, t, h, d).astype(np.float32) * 0.5
        return mk(), mk(), mk()

    def test_forward_matches_reference(self):
        from deeplearning4j_tpu.ops.attention_pallas import flash_attention
        q, k, v = self._rand()
        out = flash_attention(q, k, v, block_q=8, block_k=8, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(self._ref(q, k, v)),
                                   rtol=2e-5, atol=2e-6)

    def test_causal_matches_reference(self):
        from deeplearning4j_tpu.ops.attention_pallas import flash_attention
        q, k, v = self._rand(seed=1)
        out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(self._ref(q, k, v, True)),
                                   rtol=2e-5, atol=2e-6)

    def test_ragged_length_padding(self):
        # T not a multiple of the block: padded keys must not leak in
        from deeplearning4j_tpu.ops.attention_pallas import flash_attention
        q, k, v = self._rand(t=13, seed=2)
        out = flash_attention(q, k, v, block_q=8, block_k=8, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(self._ref(q, k, v)),
                                   rtol=2e-5, atol=2e-6)

    def test_gradients_match_reference(self):
        from deeplearning4j_tpu.ops.attention_pallas import flash_attention
        q, k, v = self._rand(b=1, t=16, h=1, d=8, seed=3)

        def loss_fused(q, k, v):
            o = flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                                interpret=True)
            return (o * o).sum()

        def loss_ref(q, k, v):
            o = self._ref(q, k, v, causal=True)
            return (o * o).sum()

        gf = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=1e-5)

    def test_bf16_inputs(self):
        import jax.numpy as jnp
        from deeplearning4j_tpu.ops.attention_pallas import flash_attention
        q, k, v = self._rand(seed=4)
        qb, kb, vb = (jnp.asarray(a, jnp.bfloat16) for a in (q, k, v))
        out = flash_attention(qb, kb, vb, block_q=8, block_k=8,
                              interpret=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(self._ref(q, k, v)),
            rtol=0.05, atol=0.02)

    def test_non_divisor_blocks(self):
        # t=20 with block_q=8, block_k=6 pads to lcm(8,6)=24
        from deeplearning4j_tpu.ops.attention_pallas import flash_attention
        q, k, v = self._rand(t=20, seed=5)
        out = flash_attention(q, k, v, block_q=8, block_k=6, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(self._ref(q, k, v)),
                                   rtol=2e-5, atol=2e-6)

    def _ref_masked(self, q, k, v, mask, causal=False):
        import jax.numpy as jnp
        d = q.shape[-1]
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        if causal:
            t = logits.shape[-1]
            logits = jnp.where(jnp.tril(jnp.ones((t, t), bool)), logits,
                               -jnp.inf)
        logits = jnp.where(jnp.asarray(mask)[:, None, None, :] > 0, logits,
                           -jnp.inf)
        w = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v)

    def test_padding_mask_matches_reference(self):
        from deeplearning4j_tpu.ops.attention_pallas import flash_attention
        q, k, v = self._rand(b=2, t=24, h=2, d=8, seed=6)
        mask = np.ones((2, 24), np.float32)
        mask[0, 17:] = 0.0    # ragged valid length, not block-aligned
        mask[1, ::3] = 0.0    # non-contiguous holes
        out = flash_attention(q, k, v, mask=jnp.asarray(mask),
                              block_q=8, block_k=8, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(self._ref_masked(q, k, v, mask)),
            rtol=2e-5, atol=2e-6)

    def test_padding_mask_causal_fully_masked_rows(self):
        """Left-padded batch under causal attention: rows before the first
        valid key see NO valid keys. The kernel emits 0 there (naive emits
        NaN); valid rows must match the naive path exactly."""
        from deeplearning4j_tpu.ops.attention_pallas import flash_attention
        q, k, v = self._rand(b=2, t=16, h=2, d=8, seed=7)
        mask = np.ones((2, 16), np.float32)
        mask[0, :5] = 0.0     # left padding: causal rows 0-4 fully masked
        out = flash_attention(q, k, v, mask=jnp.asarray(mask), causal=True,
                              block_q=8, block_k=8, interpret=True)
        ref = np.asarray(self._ref_masked(q, k, v, mask, causal=True))
        np.testing.assert_allclose(np.asarray(out)[0, 5:], ref[0, 5:],
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(out)[1], ref[1],
                                   rtol=2e-5, atol=2e-6)
        assert np.all(np.asarray(out)[0, :5] == 0.0)
        assert np.isnan(ref[0, :5]).any()   # the behavior we're fixing

    def test_padding_mask_gradients_match_reference(self):
        from deeplearning4j_tpu.ops.attention_pallas import flash_attention
        q, k, v = self._rand(b=2, t=16, h=1, d=8, seed=8)
        mask = np.ones((2, 16), np.float32)
        mask[0, 11:] = 0.0
        mask[1, :2] = 0.0
        mj = jnp.asarray(mask)

        def loss_fused(q, k, v):
            o = flash_attention(q, k, v, mask=mj, block_q=8, block_k=8,
                                interpret=True)
            return (o * o).sum()

        def loss_ref(q, k, v):
            o = self._ref_masked(q, k, v, mask)
            return (o * o).sum()

        gf = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=1e-5)

    def test_mask_dispatch_through_layer_api(self):
        """dot_product_attention with a mask and the fused path forced on
        (interpret) must agree with the naive path on valid positions."""
        from deeplearning4j_tpu.ops.attention_pallas import flash_attention
        from deeplearning4j_tpu.nn.layers.attention import \
            dot_product_attention
        q, k, v = self._rand(b=2, t=24, h=2, d=8, seed=9)
        mask = np.ones((2, 24), np.float32)
        mask[0, 20:] = 0.0
        fused = flash_attention(q, k, v, mask=jnp.asarray(mask),
                                block_q=8, block_k=8, interpret=True)
        naive = dot_product_attention(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), mask=jnp.asarray(mask))
        np.testing.assert_allclose(np.asarray(fused), np.asarray(naive),
                                   rtol=2e-5, atol=2e-6)


@pytest.fixture
def backend_gate_open(monkeypatch):
    """The one backend gate both dispatch seams read answers "tpu"."""
    monkeypatch.setattr(attention_pallas, "backend_is_tpu", lambda: True)


@pytest.mark.usefixtures("backend_gate_open")
class TestResolveAttention:
    """``resolve_attention`` is the one place that chooses between the flash
    kernel and the XLA path and that names the blocks: its answers as
    tables, a row a case (ISSUE 29)."""

    @pytest.mark.parametrize("shape,want", [
        ((4, 1024, 16, 64), (512, 512)),    # gpt2m-train-t1024's call
        ((2, 2048, 16, 128), (512, 512)),   # ouro-train-t2048's call
        ((4, 4096, 8, 64), (512, 512)),     # chip_smoke's longcontext call
        ((8, 128, 16, 64), None),           # the serving grid's short lengths:
        ((8, 512, 16, 64), None),           # XLA below the crossover
        ((1, 1023, 16, 64), None),
    ], ids=["gpt2m", "ouro", "longcontext", "t128", "t512", "t1023"])
    def test_blocks_by_shape(self, shape, want):
        for dtype in (jnp.bfloat16, jnp.float32):
            assert attention_pallas.resolve_attention(
                shape, shape, None, dtype) == want

    T = 2048
    Q = (2, T, 2, 64)

    @pytest.mark.parametrize("q,k,mask,dtype,want", [
        (Q, Q, None, np.float32, (512, 512)),
        (Q, Q, np.ones((2, T)), np.float32, (512, 512)),   # [B, Tk] padding
        (Q, Q, np.ones((2, T, T)), np.float32, None),      # a score mask
        (Q, Q, np.ones((T,)), np.float32, None),
        (Q, Q, np.ones((3, T)), np.float32, None),         # another batch
        ((2, T, 2, 256), (2, T, 2, 256), None, np.float32, (512, 512)),
        ((2, T, 2, 512), (2, T, 2, 512), None, np.float32, None),  # d > 256
        ((2, T, 2, 128), (2, T, 2, 128), None, np.float32, (512, 512)),
        ((2, 1, 2, 64), Q, None, np.float32, None),        # KV-cache decode
        (Q, (2, 2 * T, 2, 64), None, np.float32, None),    # Tq != Tk
        (Q, Q, None, np.int32, None),
        (Q, Q, None, jnp.bfloat16, (512, 512)),
    ], ids=["plain", "key_mask", "rank3_mask", "rank1_mask",
            "mask_of_another_batch", "head_256", "head_512", "head_128",
            "decode",
            "tq_ne_tk", "int32", "bf16"])
    def test_each_structural_gate(self, q, k, mask, dtype, want):
        assert attention_pallas.resolve_attention(q, k, mask, dtype) == want

    def test_a_cpu_backend_takes_the_xla_path(self, monkeypatch):
        monkeypatch.setattr(attention_pallas, "backend_is_tpu",
                            lambda: False)
        assert attention_pallas.resolve_attention(
            self.Q, self.Q, None, np.float32) is None
        # and so does the layer's seam: no kernel in what it lowers to
        from deeplearning4j_tpu.nn.layers.attention import \
            dot_product_attention
        q = jax.ShapeDtypeStruct(self.Q, jnp.float32)
        text = jax.jit(lambda q: dot_product_attention(
            q, q, q, causal=True)).lower(q).as_text()
        assert "custom_call" not in text

    def test_a_traced_scale_takes_the_xla_path(self):
        """The kernel folds a static scale; the seam checks it before it
        asks for blocks."""
        from deeplearning4j_tpu.nn.layers.attention import \
            dot_product_attention
        q = jax.ShapeDtypeStruct((1, 1024, 2, 64), jnp.float32)
        text = _lower_for_tpu(
            lambda q, s: dot_product_attention(q, q, q, scale=s), q,
            jax.ShapeDtypeStruct((), jnp.float32)).as_text()
        assert "tpu_custom_call" not in text
        text = _lower_for_tpu(
            lambda q: dot_product_attention(q, q, q, scale=0.125),
            q).as_text()
        assert 'kernel_name = "flash_attn_fwd"' in text

    def test_the_kernel_refuses_a_call_the_dispatch_leaves_to_xla(self):
        """``flash_attention`` without blocks and ``flash_attention_block``
        take ``resolve_attention``'s, and have none of their own."""
        q = jnp.zeros((1, 128, 2, 16), jnp.float32)
        with pytest.raises(ValueError, match="resolve_attention"):
            attention_pallas.flash_attention(q, q, q, interpret=True)
        with pytest.raises(ValueError, match="resolve_attention"):
            attention_pallas.flash_attention_block(q, q, q, False, 0.25,
                                                   True)


def _naive_folded(q, k, v, mask, causal, scale):
    """[BH, T, D] reference with the kernel's contract: (out, lse), the
    log-sum-exp over the keys a row may see; rows that see none are NaN
    here and 0 in the kernel. ``mask``: [BH, T] key validity or None."""
    s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
    t = s.shape[-1]
    valid = jnp.ones((1, t, t), bool)
    if causal:
        valid = valid & jnp.tril(jnp.ones((t, t), bool))[None]
    if mask is not None:
        valid = valid & (mask[:, None, :] > 0)
    s = jnp.where(valid, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), v), lse


class TestFlashKernelGeometry:
    """The forward kernel at the head's own width, walked in pieces
    (ISSUE 26): 256-wide blocks hold 2 x 2 pieces of 128, so the inner walk
    runs more than once, over tiles under, on and (skipped) above the
    diagonal. Interpret mode; Mosaic's side of it is
    TestDefaultDispatchKernelsLowerForTpu and chip_smoke.py."""

    H = 2

    def _inputs(self, t, d, masked, causal, seed):
        rs = np.random.RandomState(seed)
        q, k, v = (jnp.asarray(rs.randn(self.H, t, d).astype(np.float32)
                               * 0.5) for _ in range(3))
        mask = None
        if masked:
            m = np.ones((1, t), np.float32)
            m[0, t - 37:] = 0.0      # a padded tail, not piece-aligned
            m[0, 5::11] = 0.0        # holes
            if causal:
                m[0, :3] = 0.0       # left padding: rows 0-2 see no key
            mask = jnp.asarray(m)
        return q, k, v, mask

    @pytest.mark.parametrize("d,causal,masked,t,block_q,block_k", [
        (d, causal, masked, t, bq, 256)
        # every width on equal blocks; the unequal geometry (positions
        # compared on every piece) at width 64
        for d, bq in ((64, 256), (128, 256), (80, 256), (64, 128))
        for causal in (False, True) for masked in (False, True)
        for t in (512, 300)] + [   # whole blocks; a ragged tail
        # the step list's own cases (ISSUE 53): one step that opens and
        # closes its query block and is the head's whole grid; unequal
        # blocks over a padded T, so that a query block's last live key
        # block is not the rectangle's last
        (64, True, False, 128, 128, 128), (64, True, True, 100, 128, 128),
        (64, True, False, 700, 256, 128), (64, True, True, 700, 128, 256),
        (128, False, True, 700, 256, 128)])
    def test_forward_and_lse_match_naive(self, d, causal, masked, t,
                                         block_q, block_k):
        q, k, v, mask = self._inputs(t, d, masked, causal, seed=d + t)
        scale = 1.0 / float(d) ** 0.5
        out, lse = attention_pallas._run_fwd(
            q, k, v, mask, self.H, causal, scale, block_q, block_k, True)
        assert out.shape == (self.H, t, d) and lse.shape == (self.H, t)
        ref_out, ref_lse = _naive_folded(
            q, k, v, None if mask is None else jnp.repeat(mask, self.H, 0),
            causal, scale)
        empty = 3 if (masked and causal) else 0
        assert np.isnan(np.asarray(ref_out)[:, :empty]).all()
        assert np.all(np.asarray(out)[:, :empty] == 0.0)
        np.testing.assert_allclose(np.asarray(out)[:, empty:],
                                   np.asarray(ref_out)[:, empty:],
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(lse)[:, empty:],
                                   np.asarray(ref_lse)[:, empty:],
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("entry", ["flash_attention",
                                       "flash_attention_block"])
    def test_gradients_at_width_64(self, entry, kernel_dispatch):
        """Through the custom_vjp of both entries, the block primitive
        (on the blocks ``resolve_attention`` hands it) with a cotangent on
        its lse output too (ring attention's combination weights depend
        on it)."""
        rs = np.random.RandomState(26)
        q, k, v = (jnp.asarray(rs.randn(1, 256, 2, 64).astype(np.float32)
                               * 0.5) for _ in range(3))
        w = jnp.asarray(rs.randn(1, 2, 256).astype(np.float32))

        def fold(x):
            return x.transpose(0, 2, 1, 3).reshape(2, 256, 64)

        def naive(q, k, v):
            out, lse = _naive_folded(fold(q), fold(k), fold(v), None, True,
                                     0.125)
            return out.reshape(1, 2, 256, 64).transpose(0, 2, 1, 3), \
                lse.reshape(1, 2, 256)

        if entry == "flash_attention":
            def kernel(q, k, v):
                return attention_pallas.flash_attention(
                    q, k, v, causal=True, block_q=128, block_k=256,
                    interpret=True), 0.0
        else:
            def kernel(q, k, v):
                return attention_pallas.flash_attention_block(
                    q, k, v, True, 0.125, True)

        def loss(fn):
            def f(q, k, v):
                out, lse = fn(q, k, v)
                return jnp.sum(out * out) + jnp.sum(
                    w * lse if entry == "flash_attention_block" else 0.0)
            return f
        with kernel_dispatch():
            got = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(naive), argnums=(0, 1, 2))(q, k, v)
        for g, r, name in zip(got, want, ("dq", "dk", "dv")):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=5e-4, atol=2e-5, err_msg=name)


def _naive_folded_safe(q, k, v, mask, causal, scale):
    """``_naive_folded`` with the kernel's contract on rows that see no
    key: out 0 there, a zero gradient and no NaN (the plain softmax is NaN
    on them, and so is its gradient)."""
    s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
    t = s.shape[-1]
    valid = jnp.ones((1, t, t), bool)
    if causal:
        valid = valid & jnp.tril(jnp.ones((t, t), bool))[None]
    if mask is not None:
        valid = valid & (mask[:, None, :] > 0)
    s = jnp.where(valid, s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(valid, jnp.exp(s - m), 0.0)
    l = jnp.sum(e, axis=-1, keepdims=True)
    out = jnp.einsum("bqk,bkd->bqd", e / jnp.maximum(l, 1e-30), v)
    return out, (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]


class TestFlashBackwardKernel:
    """The backward kernel (ISSUE 28) in interpret mode against jax.grad of
    the naive path in float32, called where the custom_vjps call it
    (``_run_bwd_local``) so that both of its forms are reached at a small
    T: "fused" (one kernel a head, dq resident) and "split" (a dK/dV and a
    dQ kernel), which ``_run_bwd`` chooses between from the shape. Every
    case carries a cotangent on lse too, as ``flash_attention_block``'s
    does. Mosaic's side is TestDefaultDispatchKernelsLowerForTpu."""

    H = 2

    def _case(self, d, causal, masked, t, seed):
        rs = np.random.RandomState(seed)
        q, k, v, g = (jnp.asarray(rs.randn(self.H, t, d).astype(np.float32)
                                  * 0.5) for _ in range(4))
        g_lse = rs.randn(self.H, t).astype(np.float32)
        mask, empty = None, 0
        if masked:
            m = np.ones((1, t), np.float32)
            m[0, t - t // 8:] = 0.0      # a padded tail, not piece-aligned
            m[0, 5::11] = 0.0            # holes
            if causal:
                m[0, :3] = 0.0           # left padding: rows 0-2 see no key
                empty = 3
            mask = jnp.asarray(m)
        g_lse[:, :empty] = 0.0           # their lse is the sentinel
        scale = 1.0 / float(d) ** 0.5
        maskh = None if mask is None else jnp.repeat(mask, self.H, 0)
        (out, lse), vjp = jax.vjp(
            lambda q, k, v: _naive_folded_safe(q, k, v, maskh, causal, scale),
            q, k, v)
        g_lse = jnp.asarray(g_lse)
        return (q, k, v, out, lse, g, g_lse, mask), vjp((g, g_lse)), empty

    def _check(self, got, want, empty):
        for a, b, name in zip(got, want, ("dq", "dk", "dv")):
            assert np.isfinite(np.asarray(a)).all(), name
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=2e-5, err_msg=name)
        # rows that see no key: out is 0 there whatever q is
        assert np.all(np.asarray(got[0])[:, :empty] == 0.0)

    @pytest.mark.parametrize("d,causal,masked,t,block_q,block_k,form", [
        (d, causal, masked, t, bq, bk, form)
        # every width on equal blocks, fused; both unequal geometries
        # (positions compared on every piece) and the split form at width 64
        for d, bq, bk, form in ((64, 256, 256, "fused"),
                                (128, 256, 256, "fused"),
                                (80, 256, 256, "fused"),
                                (64, 128, 256, "fused"),
                                (64, 256, 128, "split"),
                                (64, 256, 256, "split"))
        for causal in (False, True) for masked in (False, True)
        for t in (512, 300)] + [   # whole blocks; a ragged tail
        # the width the two width-256 cells run fused from ISSUE 46
        (256, True, masked, t, 256, 256, form)
        for masked, form in ((False, "fused"), (True, "fused"),
                             (False, "split"))
        for t in (512, 300)] + [
        # the step list's own cases (ISSUE 53). One tile a head: its one
        # step opens and closes the key block (dkv), the query block (dq)
        # and the head's dq (fused) at once
        (64, True, False, 128, 128, 128, "fused"),
        (64, True, True, 100, 128, 128, "split"),
        # unequal blocks over a padded T (768): under causal the first
        # query block's only key block, and a key block's first live query
        # block, are not the rectangle's first or last
        (64, True, False, 700, 256, 128, "fused"),
        (64, True, True, 700, 256, 128, "split"),
        (64, True, True, 700, 128, 256, "fused"),
        (64, True, False, 700, 128, 256, "split"),
        (128, False, True, 700, 256, 128, "fused")])
    def test_gradients_match_naive(self, d, causal, masked, t, block_q,
                                   block_k, form):
        args, want, empty = self._case(d, causal, masked, t, seed=d + t)
        got = attention_pallas._run_bwd_local(
            *args, self.H, causal, 1.0 / float(d) ** 0.5, block_q, block_k,
            True, form)
        self._check(got, want, empty)

    @pytest.mark.parametrize("t,block_q,block_k,causal,masked,form", [
        (24, 8, 8, True, False, "fused"), (24, 8, 8, True, True, "split"),
        (20, 8, 6, True, True, "fused"), (20, 8, 6, False, False, "split"),
        (13, 8, 8, False, True, "fused"), (20, 6, 8, True, False, "fused")])
    def test_blocks_that_are_not_128s(self, t, block_q, block_k, causal,
                                      masked, form):
        """A block of 8 or 6 is its own single piece; t 20 pads to
        lcm(8, 6) = 24."""
        args, want, empty = self._case(8, causal, masked, t, seed=t)
        got = attention_pallas._run_bwd_local(
            *args, self.H, causal, 1.0 / 8.0 ** 0.5, block_q, block_k, True,
            form)
        self._check(got, want, empty)

    @pytest.mark.parametrize("wrong", ["no_delta", "no_causal_mask"])
    def test_a_wrong_kernel_fails_the_check(self, wrong):
        """The control: the comparison above refuses a backward that drops
        the delta term of ds = p * (dp - delta) (out = 0 makes delta 0) and
        one that walks the tiles as if the call were not causal."""
        (q, k, v, out, lse, g, g_lse, mask), want, empty = self._case(
            64, True, False, 512, seed=28)
        if wrong == "no_delta":
            out, g_lse = jnp.zeros_like(out), None
        got = attention_pallas._run_bwd_local(
            q, k, v, out, lse, g, g_lse, mask, self.H,
            wrong != "no_causal_mask", 0.125, 256, 256, True, "fused")
        with pytest.raises(AssertionError):
            self._check(got, want, empty)

    @pytest.mark.parametrize("t,d,dtype,interpret,form", [
        (1024, 64, "float32", False, "fused"),    # gpt2m-train-t1024's call
        (2048, 128, "float32", False, "fused"),   # ouro-train-t2048's
        (8192, 64, "float32", False, "fused"),    # lfm2-train-t8192's
        (4096, 128, "float32", False, "fused"),   # nemotron3nano's
        (4096, 64, "bfloat16", False, "fused"),   # the longcontext one's
        # from ISSUE 46, timed in both forms on the chip first (PERF.md):
        (4096, 256, "float32", False, "fused"),   # glm47flash's, qwen3next's
        (2048, 256, "float32", False, "fused"),   # chip_smoke.py's 3 layers
        (4096, 256, "bfloat16", False, "fused"),
        (8192, 128, "float32", False, "fused"),   # past the default limit
        # past the budget: nobody timed the fused form there
        (8192, 256, "float32", False, "split"),
        (16384, 64, "float32", False, "split"),   # 64 wide costs 128 lanes
        (65536, 128, "bfloat16", False, "split"),
        # the interpreter multiplies in float32: 4 B operands, 21.06 MiB
        (4096, 256, "float32", True, "split")])
    def test_form_follows_the_shape(self, monkeypatch, t, d, dtype,
                                    interpret, form):
        """``_run_bwd`` takes the fused form while what it holds in VMEM,
        operands at the residuals' size and gradients at the cotangent's,
        is within the budget and the split one past that; the count it
        decides by is the one ``_run_bwd_local`` asks Mosaic for."""
        seen = []
        monkeypatch.setattr(
            attention_pallas, "_run_bwd_local",
            lambda *a: seen.append(a[14]) or (a[5], a[5], a[5]))
        dtype = jnp.dtype(dtype)
        # the residuals are what the forward kernel read; the cotangent
        # and the forward's output are the caller's
        cd = attention_pallas._operand_dtype(dtype, interpret)
        x = jax.ShapeDtypeStruct((2, t, d), cd)
        g = jax.ShapeDtypeStruct((2, t, d), dtype)
        lse = jax.ShapeDtypeStruct((2, t), jnp.float32)
        grads = jax.eval_shape(
            lambda q, g, lse: attention_pallas._run_bwd(
                (q, q, q, None, g, lse), g, None, 1, True, 0.125, 512, 512,
                interpret), x, g, lse)
        assert seen == [form]
        assert all(a.dtype == dtype for a in grads)
        sizes = (cd.itemsize, dtype.itemsize)
        assert sizes[0] == (4 if interpret else 2)
        fused = attention_pallas.bwd_vmem_bytes("fused", t, d, 512, 512,
                                                *sizes)
        assert (fused <= attention_pallas._VMEM_BUDGET) == (form == "fused")
        assert attention_pallas.bwd_vmem_bytes(
            "split", t, d, 512, 512, *sizes) < 10 * 2 ** 20

    @pytest.mark.parametrize("t,d,sizes,mib", [
        (4096, 256, (2, 4), 19.0625),   # operands 2, stats 0.06, pieces 2,
                                        # dk/dv 2 + 1, dq 8 + 4
        (4096, 256, (4, 4), 21.0625),   # what ISSUE 46 found it counting
        (8192, 128, (2, 4), 16.5625),   # the compiler's own said 16.46
        (2048, 256, (2, 4), 13.0625),
        (8192, 64, (2, 4), 14.3125),    # in whole lane tiles: as 128 wide,
        (16384, 64, (2, 4), 24.3125),   # but for the [64, T] accumulator
        (4096, 256, (2, 2), 14.0625)])
    def test_the_count_is_pinned(self, t, d, sizes, mib):
        assert attention_pallas.bwd_vmem_bytes(
            "fused", t, d, 512, 512, *sizes) == int(mib * 2 ** 20)

    @staticmethod
    def _backward_calls(t, d, dtype=jnp.float32, interpret=False):
        """The ``pallas_call`` equations of one backward for a caller in
        ``dtype``, through ``_run_bwd`` (traced, never lowered: the CPU has
        no Mosaic)."""
        x = jax.ShapeDtypeStruct(
            (2, t, d), attention_pallas._operand_dtype(dtype, interpret))
        g = jax.ShapeDtypeStruct((2, t, d), dtype)
        lse = jax.ShapeDtypeStruct((2, t), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda q, g, lse: attention_pallas._run_bwd(
                (q, q, q, None, g, lse), g, None, 1, True, 0.125, 512, 512,
                interpret))(x, g, lse)
        return [e for e in jaxpr.jaxpr.eqns
                if e.primitive.name == "pallas_call"]

    @pytest.mark.parametrize("t,d,names", [
        (1024, 64, ["flash_attn_bwd_fused"]),
        (8192, 64, ["flash_attn_bwd_fused"]),
        (4096, 128, ["flash_attn_bwd_fused"]),
        (2048, 256, ["flash_attn_bwd_fused"]),
        (8192, 256, ["flash_attn_bwd_dkv", "flash_attn_bwd_dq"])])
    def test_under_the_default_limit_mosaic_is_asked_for_nothing(
            self, t, d, names):
        """The cells whose backward was fused before ISSUE 46 keep the call
        they had, and so does every split call."""
        calls = self._backward_calls(t, d)
        assert [e.params["name"] for e in calls] == names
        assert all(not e.params["compiler_params"] for e in calls)

    @pytest.mark.parametrize("t,d", [(4096, 256), (8192, 128)])
    def test_past_the_default_limit_the_fused_call_asks_for_its_count(
            self, t, d):
        (call,) = self._backward_calls(t, d)
        assert call.params["name"] == "flash_attn_bwd_fused"
        asked = call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
        count = attention_pallas.bwd_vmem_bytes("fused", t, d, 512, 512, 2, 4)
        assert attention_pallas._VMEM_DEFAULT < count \
            + attention_pallas._VMEM_MARGIN == asked
        assert asked <= attention_pallas._VMEM_BUDGET \
            + attention_pallas._VMEM_MARGIN

    def test_custom_vjp_reaches_the_split_form(self, monkeypatch):
        """Through ``flash_attention``'s custom_vjp with the budget at 0:
        the two kernels' gradients are the fused kernel's."""
        rs = np.random.RandomState(7)
        q, k, v = (jnp.asarray(rs.randn(1, 160, 2, 16).astype(np.float32))
                   for _ in range(3))

        def grads():
            return jax.grad(lambda q, k, v: jnp.sum(
                attention_pallas.flash_attention(
                    q, k, v, causal=True, block_q=128, block_k=128,
                    interpret=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        fused = grads()
        monkeypatch.setattr(attention_pallas, "_VMEM_BUDGET", 0)
        names = str(jax.make_jaxpr(grads)())
        assert "flash_attn_bwd_dkv" in names and "flash_attn_bwd_dq" in names
        assert "flash_attn_bwd_fused" not in names
        for a, b in zip(grads(), fused):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


def _ref_scan_any(xz, wh, h0, c0, wp=None, mask=None):
    """Scan reference covering peephole x mask (mask time-major [T, B],
    1=valid: state freezes at padded steps — nn/layers/rnn.py _step)."""
    def step(carry, inp):
        xz_t, m_t = inp
        h_prev, c_prev = carry
        z = xz_t + h_prev @ wh
        zi, zf, zg, zo = jnp.split(z, 4, -1)
        if wp is not None:
            zi = zi + wp[0] * c_prev
            zf = zf + wp[1] * c_prev
        c = jax.nn.sigmoid(zf) * c_prev + jax.nn.sigmoid(zi) * jnp.tanh(zg)
        if wp is not None:
            zo = zo + wp[2] * c
        h = jax.nn.sigmoid(zo) * jnp.tanh(c)
        if m_t is not None:
            m = m_t[:, None]
            h = m * h + (1 - m) * h_prev
            c = m * c + (1 - m) * c_prev
        return (h, c), h
    ms = jnp.ones(xz.shape[:2], xz.dtype) if mask is None else mask
    (hT, cT), hs = jax.lax.scan(
        lambda ca, inp: step(ca, (inp[0], inp[1])), (h0, c0), (xz, ms))
    return hs, (hT, cT)


class TestMaskedAndTiledPeepholeLstm:
    """VERDICT r3 #4: masked sequences on every fused path, peephole on
    the tiled large-H path. Numerics pinned vs the scan reference in
    interpret mode."""

    def _mask(self, T, B, seed):
        rs = np.random.RandomState(seed)
        lens = rs.randint(1, T + 1, B)
        m = (np.arange(T)[:, None] < lens[None, :]).astype(np.float32)
        return jnp.asarray(m)  # time-major [T, B]

    def test_masked_forward_matches_scan(self):
        xz, wh, h0, c0 = _inputs(T=5, B=8, H=128, seed=21)
        mask = self._mask(5, 8, 21)
        hs_f, (hT_f, cT_f) = lstm_pallas.fused_sequence_padded(
            xz, wh, h0, c0, mask=mask, interpret=True)
        hs_r, (hT_r, cT_r) = _ref_scan_any(xz, wh, h0, c0, mask=mask)
        np.testing.assert_allclose(np.asarray(hs_f), np.asarray(hs_r),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(hT_f), np.asarray(hT_r),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(cT_f), np.asarray(cT_r),
                                   atol=1e-5)

    def test_masked_peephole_forward_matches_scan(self):
        xz, wh, h0, c0 = _inputs(T=4, B=8, H=128, seed=22)
        rs = np.random.RandomState(122)
        wp = jnp.asarray(rs.randn(3, 128).astype(np.float32) * 0.1)
        mask = self._mask(4, 8, 22)
        hs_f, (hT_f, cT_f) = lstm_pallas.fused_sequence_padded(
            xz, wh, h0, c0, wp=wp, mask=mask, interpret=True)
        hs_r, (hT_r, cT_r) = _ref_scan_any(xz, wh, h0, c0, wp=wp, mask=mask)
        np.testing.assert_allclose(np.asarray(hs_f), np.asarray(hs_r),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(cT_f), np.asarray(cT_r),
                                   atol=1e-5)

    def test_masked_gradients_match_scan(self):
        xz, wh, h0, c0 = _inputs(T=4, B=8, H=100, seed=23)  # lane-padded H
        mask = self._mask(4, 8, 23)

        def make_loss(fn):
            def loss(xz, wh, h0, c0):
                hs, (hT, cT) = fn(xz, wh, h0, c0)
                return (jnp.sum((hs * mask[..., None]) ** 2)
                        + jnp.sum(jnp.tanh(hT)) + jnp.sum(cT ** 2))
            return loss

        gp = jax.grad(make_loss(
            lambda *a: lstm_pallas.fused_sequence_padded(
                *a, mask=mask, interpret=True)),
            argnums=(0, 1, 2, 3))(xz, wh, h0, c0)
        gr = jax.grad(make_loss(
            lambda *a: _ref_scan_any(*a, mask=mask)),
            argnums=(0, 1, 2, 3))(xz, wh, h0, c0)
        for p, r, name in zip(gp, gr, ("dxz", "dwh", "dh0", "dc0")):
            np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                       atol=2e-5, err_msg=name)

    @pytest.mark.slow
    def test_tiled_peephole_forward_matches_scan_h640(self):
        xz, wh, h0, c0 = _inputs(T=2, B=8, H=640, seed=24)
        rs = np.random.RandomState(124)
        wp = jnp.asarray(rs.randn(3, 640).astype(np.float32) * 0.1)
        hs_f, (hT_f, cT_f) = lstm_pallas.lstm_fused_sequence_peephole(
            xz, wh, wp, h0, c0, True)
        hs_r, (hT_r, cT_r) = _ref_scan_any(xz, wh, h0, c0, wp=wp)
        np.testing.assert_allclose(np.asarray(hs_f), np.asarray(hs_r),
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(cT_f), np.asarray(cT_r),
                                   atol=1e-4)

    @pytest.mark.slow
    def test_tiled_peephole_gradients_match_scan_h640(self):
        xz, wh, h0, c0 = _inputs(T=2, B=8, H=640, seed=25)
        rs = np.random.RandomState(125)
        wp = jnp.asarray(rs.randn(3, 640).astype(np.float32) * 0.1)

        def make_loss(fn):
            def loss(xz, wh, wp, h0, c0):
                hs, (hT, cT) = fn(xz, wh, wp, h0, c0)
                return jnp.sum(hs ** 2) + jnp.sum(cT ** 2)
            return loss

        gp = jax.grad(make_loss(
            lambda *a: lstm_pallas.lstm_fused_sequence_peephole(*a, True)),
            argnums=(0, 1, 2, 3, 4))(xz, wh, wp, h0, c0)
        gr = jax.grad(make_loss(
            lambda xz, wh, wp, h0, c0: _ref_scan_any(xz, wh, h0, c0, wp=wp)),
            argnums=(0, 1, 2, 3, 4))(xz, wh, wp, h0, c0)
        for p, r, name in zip(gp, gr, ("dxz", "dwh", "dwp", "dh0", "dc0")):
            np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                       atol=5e-4, err_msg=name)

    @pytest.mark.slow
    def test_tiled_masked_forward_matches_scan_h640(self):
        xz, wh, h0, c0 = _inputs(T=3, B=8, H=640, seed=26)
        mask = self._mask(3, 8, 26)
        hs_f, (hT_f, cT_f) = lstm_pallas.fused_sequence_padded(
            xz, wh, h0, c0, mask=mask, interpret=True)
        hs_r, (hT_r, cT_r) = _ref_scan_any(xz, wh, h0, c0, mask=mask)
        np.testing.assert_allclose(np.asarray(hs_f), np.asarray(hs_r),
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(cT_f), np.asarray(cT_r),
                                   atol=1e-4)

    def test_layer_masked_batch_uses_kernel_path(self, monkeypatch):
        """The LSTM layer's masked-batch output is identical between the
        scan path and the fused path (via the supported() contract —
        dispatch itself is TPU-gated, so pin the layer's scan result to
        the kernel called directly)."""
        from deeplearning4j_tpu.nn import layers as L
        layer = L.LSTM(n_out=128)
        it = __import__("deeplearning4j_tpu.nn.conf.inputs",
                        fromlist=["RecurrentType"]).RecurrentType(16, 4)
        p = layer.init(jax.random.PRNGKey(0), it)
        rs = np.random.RandomState(27)
        x = jnp.asarray(rs.randn(8, 4, 16).astype(np.float32))
        mask_bm = jnp.asarray(
            (np.arange(4)[None, :] < rs.randint(1, 5, 8)[:, None])
            .astype(np.float32))
        y_scan, _ = layer.apply(p, {}, x, mask=mask_bm)
        b, t, _ = x.shape
        xz = (x.reshape(b * t, -1) @ p["Wx"] + p["b"]).reshape(
            b, t, 4 * 128).transpose(1, 0, 2)
        h0 = jnp.zeros((b, 128)); c0 = jnp.zeros((b, 128))
        hs, _ = lstm_pallas.fused_sequence_padded(
            xz, p["Wh"], h0, c0, mask=mask_bm.transpose(1, 0),
            interpret=True)
        y_kern = hs.transpose(1, 0, 2) * mask_bm[..., None]
        np.testing.assert_allclose(np.asarray(y_scan), np.asarray(y_kern),
                                   atol=1e-5)

    @pytest.mark.slow
    def test_masked_peephole_gradients_match_scan(self):
        xz, wh, h0, c0 = _inputs(T=4, B=8, H=128, seed=28)
        rs = np.random.RandomState(128)
        wp = jnp.asarray(rs.randn(3, 128).astype(np.float32) * 0.1)
        mask = self._mask(4, 8, 28)

        def make_loss(fn):
            def loss(xz, wh, wp, h0, c0):
                hs, (hT, cT) = fn(xz, wh, wp, h0, c0)
                return (jnp.sum((hs * mask[..., None]) ** 2)
                        + jnp.sum(cT ** 2))
            return loss

        gp = jax.grad(make_loss(
            lambda xz, wh, wp, h0, c0: lstm_pallas.fused_sequence_padded(
                xz, wh, h0, c0, wp=wp, mask=mask, interpret=True)),
            argnums=(0, 1, 2, 3, 4))(xz, wh, wp, h0, c0)
        gr = jax.grad(make_loss(
            lambda xz, wh, wp, h0, c0: _ref_scan_any(
                xz, wh, h0, c0, wp=wp, mask=mask)),
            argnums=(0, 1, 2, 3, 4))(xz, wh, wp, h0, c0)
        for p, r, name in zip(gp, gr, ("dxz", "dwh", "dwp", "dh0", "dc0")):
            np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                       atol=5e-5, err_msg=name)

    @pytest.mark.slow
    def test_tiled_masked_gradients_match_scan_h640(self):
        xz, wh, h0, c0 = _inputs(T=2, B=8, H=640, seed=29)
        mask = self._mask(2, 8, 29)

        def make_loss(fn):
            def loss(xz, wh, h0, c0):
                hs, (hT, cT) = fn(xz, wh, h0, c0)
                return jnp.sum(hs ** 2) + jnp.sum(cT ** 2)
            return loss

        gp = jax.grad(make_loss(
            lambda *a: lstm_pallas.fused_sequence_padded(
                *a, mask=mask, interpret=True)),
            argnums=(0, 1, 2, 3))(xz, wh, h0, c0)
        gr = jax.grad(make_loss(
            lambda *a: _ref_scan_any(*a, mask=mask)),
            argnums=(0, 1, 2, 3))(xz, wh, h0, c0)
        for p, r, name in zip(gp, gr, ("dxz", "dwh", "dh0", "dc0")):
            np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                       atol=5e-4, err_msg=name)


# ---------------------------------------------------------------------------
# TPU lowering, checked on the CPU (ISSUE 21): interpret mode checks the
# arithmetic of a kernel but none of the TPU's block-shape rules, so a spec
# the chip refuses (the masked LSTM's (1, B) block of a [T, B] array) passed
# every interpret test and was default-dispatched on the chip. Lowering for
# the TPU platform runs the Pallas->Mosaic lowering that applies those rules
# and needs no chip.
# ---------------------------------------------------------------------------

def _lower_for_tpu(fn, *args, **jit_kw):
    # the chip runs with 32-bit defaults; conftest's float64 mode would put
    # f64 constants into the kernel body, which Mosaic refuses to cast
    with jax.enable_x64(False):
        return jax.jit(fn, **jit_kw).trace(*args).lower(
            lowering_platforms=("tpu",))


def _after_the_step_list(operands, live):
    """A flash kernel's operands behind the three ``int32[live]`` arrays of
    its step list (``attention_pallas.step_list``), which lead them."""
    lists = ", ".join([f"tensor<{live}xi32>"] * 3) + ", "
    assert operands.startswith(lists), operands
    return operands[len(lists):]


def _lstm_loss(peephole, masked):
    def loss(xz, wh, h0, c0, wp, mask):
        hs, (_, cT) = lstm_pallas.fused_sequence_padded(
            xz, wh, h0, c0, wp=wp if peephole else None,
            mask=mask if masked else None)
        return jnp.sum(hs.astype(jnp.float32)) + jnp.sum(
            cT.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1))


@pytest.mark.usefixtures("backend_gate_open")
class TestDefaultDispatchKernelsLowerForTpu:
    """Every Pallas kernel the default dispatch can reach, lowered for the
    TPU platform at the dispatch gates' real shapes (bf16, fwd + bwd), on
    the blocks ``resolve_attention`` gives with its backend gate open."""

    @pytest.mark.parametrize("t,b,hsz,peephole,masked", [
        (128, 64, 512, False, False),     # resident, the bench lstm shape
        (128, 64, 512, True, False),      # GravesLSTM
        (128, 64, 512, False, True),      # the spec the chip refused
        (128, 64, 512, True, True),
        (128, 12, 512, True, True),       # B off the 8-sublane multiple
        (128, 64, 200, True, True),       # lane-padded H
        (32, 64, 1024, False, False),     # tiled
        (32, 64, 1024, True, True),
        (16, 8, 2048, False, True),
    ])
    def test_lstm(self, t, b, hsz, peephole, masked):
        bf = jnp.bfloat16
        args = (jnp.zeros((t, b, 4 * hsz), bf), jnp.zeros((hsz, 4 * hsz), bf),
                jnp.zeros((b, hsz), bf), jnp.zeros((b, hsz), bf),
                jnp.zeros((3, hsz), bf), jnp.ones((t, b), jnp.float32))
        assert lstm_pallas.supported(
            (b, t, 32), hsz, peephole=peephole,
            mask=np.ones((b, t)) if masked else None,
            gate_activation="sigmoid", activation="tanh")
        text = _lower_for_tpu(_lstm_loss(peephole, masked), *args).as_text()
        assert "tpu_custom_call" in text

    @pytest.mark.parametrize("b,t,h,d,causal,masked", [
        (4, 1024, 16, 64, True, False),   # the gpt2m-train-t1024 cell's
        (4, 4096, 8, 64, True, False),    # the longcontext shape
        (1, 2048, 4, 128, True, False),   # full-lane head dim
        (1, 4096, 16, 256, True, False),  # the qwen3next-train-t4096 cell's
        (4, 1024, 8, 64, False, True),    # [B, Tk] key-padding mask
        (2, 3000, 8, 64, True, True),     # ragged T, padded inside
    ])
    def test_flash_attention(self, b, t, h, d, causal, masked):
        q = jnp.zeros((b, t, h, d), jnp.bfloat16)
        mask = jnp.ones((b, t), jnp.float32) if masked else None
        assert attention_pallas.resolve_attention(
            q.shape, q.shape, mask, q.dtype) == (512, 512)

        def loss(q, k, v):
            return jnp.sum(attention_pallas.flash_attention(
                q, k, v, mask=mask, causal=causal).astype(jnp.float32))
        text = _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)),
                              q, q, q).as_text()
        assert "tpu_custom_call" in text
        # the forward kernel's q, k, v operands keep the head's own width:
        # no pad of d to the 128 lanes in front of the custom call
        call = next(ln for ln in text.splitlines()
                    if 'kernel_name = "flash_attn_fwd"' in ln)
        t_pad = -(-t // 512) * 512
        operands = _after_the_step_list(
            call.split(" : (")[1].split(") -> ")[0],
            attention_pallas.step_list(causal, None, t_pad // 512,
                                       t_pad // 512, 512, 512).live)
        assert operands.startswith(
            ", ".join([f"tensor<{b * h}x{t_pad}x{d}xbf16>"] * 3)), operands

    @pytest.mark.parametrize("b,t,h,d,kernels", [
        (4, 1024, 16, 64, ("fused",)),    # the gpt2m-train-t1024 cell's
        (2, 2048, 16, 128, ("fused",)),   # the ouro-train-t2048 cell's
        # past the default limit, Mosaic asked for the count (ISSUE 46):
        (1, 8192, 2, 128, ("fused",)),
        (1, 4096, 16, 256, ("fused",)),   # the qwen3next-train-t4096 cell's
        (1, 4096, 20, 256, ("fused",)),   # the glm47flash-train-t4096 cell's
        (1, 8192, 2, 256, ("dkv", "dq")),  # a head's dq past the budget
    ])
    def test_flash_backward_is_a_kernel_under_its_scope(self, b, t, h, d,
                                                        kernels):
        """float32 operands, as the cells hand them: the gradient holds
        the backward kernel(s) under ``flash_attn.bwd`` and no loop."""
        q = jnp.zeros((b, t, h, d), jnp.float32)

        def loss(q, k, v):
            return jnp.sum(attention_pallas.flash_attention(
                q, k, v, causal=True))
        text = _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)),
                              q, q, q).as_text(debug_info=True)
        assert "stablehlo.while" not in text
        calls = re.findall(r'kernel_name = "(flash_attn_[a-z_]+)"', text)
        assert sorted(calls) == sorted(
            ["flash_attn_fwd"] + ["flash_attn_bwd_" + k for k in kernels])
        paths = set(re.findall(r'loc\("([^"]+)"', text))
        for k in kernels:
            assert any("transpose(" in p and "flash_attn.bwd" in p
                       and "/flash_attn_bwd_" + k + "/" in p
                       for p in paths), k
            # the matrix units round a float32 operand to bfloat16 as they
            # take it, so the backward is handed q, k, v, g already rounded
            # (half the residuals kept from the forward; the chip's results
            # are the same to the bit, PERF.md PR 28) and returns float32
            call = next(ln for ln in text.splitlines()
                        if f'kernel_name = "flash_attn_bwd_{k}"' in ln)
            operands, results = call.split(" : (")[1].split(") -> ")
            operands = _after_the_step_list(
                operands, attention_pallas.step_list(
                    True, None, t // 512, t // 512, 512, 512).live)
            assert operands.startswith(
                ", ".join([f"tensor<{b * h}x{t}x{d}xbf16>"] * 4)), operands
            assert "bf16" not in results and f"x{d}xf32>" in results, results

    @staticmethod
    def _attention_loss(entry, interpret=False):
        """The sum of an entry's outputs, through the dispatch (the layers'
        call) or the ring block: every output takes a cotangent."""
        from deeplearning4j_tpu.nn.layers.attention import \
            dot_product_attention

        def loss(q, k, v):
            if entry == "dot_product_attention":
                return jnp.sum(dot_product_attention(q, k, v, causal=True))
            out, lse = attention_pallas.flash_attention_block(
                q, k, v, True, 0.125, interpret)
            return jnp.sum(out) + jnp.sum(lse).astype(out.dtype)
        return jax.grad(loss, argnums=(0, 1, 2))

    @pytest.mark.parametrize("entry", ["dot_product_attention",
                                       "flash_attention_block"])
    @pytest.mark.parametrize("dtype,rounded", [("float32", 4),
                                               ("bfloat16", 0)])
    def test_both_flash_kernels_read_one_rounded_copy(self, entry, dtype,
                                                      rounded):
        """ISSUE 47. From float32 callers q, k and v are rounded to
        bfloat16 once, in the forward's rule: the forward kernel and the
        backward kernel take those, the cotangent is the fourth and last
        rounding, and the forward's result and the three gradients are
        float32. From bfloat16 callers nothing is rounded."""
        b, t, h, d = 1, 1024, 2, 64
        q = jnp.zeros((b, t, h, d), dtype)
        text = _lower_for_tpu(self._attention_loss(entry), q, q, q).as_text()
        head = f"tensor<{b * h}x{t}x{d}x"
        name = {"float32": "f32", "bfloat16": "bf16"}[dtype]
        calls = {}
        for ln in text.splitlines():
            m = re.search(r'kernel_name = "(flash_attn_[a-z_]+)"', ln)
            if m:
                operands, results = ln.split(" : (")[1].split(") -> ")
                # T 1,024 causal in 512 x 512 blocks: 3 live steps of 4
                calls[m.group(1)] = (_after_the_step_list(operands, 3),
                                     results)
        assert sorted(calls) == ["flash_attn_bwd_fused", "flash_attn_fwd"]
        operands, results = calls["flash_attn_fwd"]
        assert operands == ", ".join([head + "bf16>"] * 3), operands
        assert results.startswith(f"({head}{name}>, "), results
        operands, results = calls["flash_attn_bwd_fused"]
        assert operands.startswith(", ".join([head + "bf16>"] * 4)), operands
        assert results.count(f"{head}{name}>") == 3, results
        assert len(re.findall(
            r"stablehlo\.convert [^\n]*-> " + re.escape(head + "bf16>"),
            text)) == rounded

    @pytest.mark.parametrize("entry", ["dot_product_attention",
                                       "flash_attention_block"])
    @pytest.mark.parametrize("dtype,interpret,operand", [
        ("float64", False, "float64"),    # keeps its width
        ("float32", True, "float32"),     # the interpreter multiplies in f32
        ("bfloat16", True, "bfloat16"),
        ("float32", False, "bfloat16")])  # the control: the chip's rule
    def test_flash_operands_keep_their_dtype_off_the_matrix_units(
            self, monkeypatch, entry, dtype, interpret, operand):
        """Traced, not lowered (the interpreter and float64 are not the
        chip's): what each ``pallas_call`` reads and writes."""
        if interpret:
            flash = attention_pallas.flash_attention
            monkeypatch.setattr(
                attention_pallas, "flash_attention",
                lambda *a, **kw: flash(*a, **kw, interpret=True))
        q = jax.ShapeDtypeStruct((1, 1024, 2, 64), jnp.dtype(dtype))
        jaxpr = jax.make_jaxpr(self._attention_loss(entry, interpret))(
            q, q, q)
        calls = {e.params["name"]: e for e in jaxpr.jaxpr.eqns
                 if e.primitive.name == "pallas_call"}
        assert sorted(calls) == ["flash_attn_bwd_fused", "flash_attn_fwd"]
        fwd, bwd = calls["flash_attn_fwd"], calls["flash_attn_bwd_fused"]
        # the step list's three int32 arrays lead every call's operands
        for call in (fwd, bwd):
            assert [str(a.aval.dtype) for a in call.invars[:3]] \
                == ["int32"] * 3
        assert [str(a.aval.dtype) for a in fwd.invars[3:]] == [operand] * 3
        assert [str(a.aval.dtype) for a in bwd.invars[3:7]] == [operand] * 4
        # the same arrays, not a second copy: the backward's q, k, v are
        # the forward's operands
        assert bwd.invars[3:6] == fwd.invars[3:]
        assert str(fwd.outvars[0].aval.dtype) == dtype
        assert [str(a.aval.dtype) for a in bwd.outvars] == [dtype] * 3

    @pytest.mark.parametrize("b,t,hk,hv,d,dtype", [
        (1, 4096, 16, 32, 128, jnp.float32),  # the qwen3next-train-t4096 cell's
        (2, 1000, 2, 8, 256, jnp.bfloat16),   # ragged T, four heads of 256
    ])
    def test_gated_delta_rule(self, b, t, hk, hv, d, dtype):
        """Through the dispatch: the recurrence and its gradient are the
        two kernels, once each, and no loop over the chunk states."""
        from deeplearning4j_tpu.ops import gated_delta
        q = jnp.zeros((b, t, hk, d), dtype)
        v = jnp.zeros((b, t, hv, d), dtype)
        g = jnp.zeros((b, t, hv), jnp.float32)
        assert gated_delta.resolve_gated_delta(q.shape, v.shape, dtype)

        def loss(q, k, v, g, beta):
            return jnp.sum(gated_delta.gated_delta_rule(
                q, k, v, g, beta).astype(jnp.float32))
        text = _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                              q, q, v, g, g).as_text()
        assert "stablehlo.while" not in text
        assert sorted(re.findall(r'kernel_name = "(gdn_[a-z]+)"', text)) == [
            "gdn_bwd", "gdn_fwd"]

    @pytest.mark.parametrize(
        "b,t,width,columns,taps,gate_before,gate_after,activation,split,"
        "dtype", [
            # the qwen3next-train-t4096 cell's: [q | k | v] of x W_qkvz
            (1, 4096, 12288, 8192, 4, False, False, True,
             (2048, 2048, 4096), jnp.float32),
            # the lfm2-train-t8192 cell's: [B | C | x] of x W_in
            (1, 8192, 6144, 2048, 3, True, True, False, (), jnp.float32),
            # ragged T, packed rows, one gate, columns behind the parts
            (2, 1000, 1024, 384, 4, True, False, True, (256, 128),
             jnp.bfloat16),
        ])
    def test_causal_conv(self, b, t, width, columns, taps, gate_before,
                         gate_after, activation, split, dtype):
        """Through the dispatch: the convolution with its gates and SiLU
        and its gradient are the two kernels, once each."""
        from deeplearning4j_tpu.ops import causal_conv
        p = jnp.zeros((b, t, width), dtype)
        w = jnp.zeros((columns, taps), dtype)
        assert causal_conv.resolve_causal_conv(
            p.shape, w.shape, dtype, gate_before, gate_after, split)

        def loss(p, w):
            y, rest = causal_conv.causal_conv(
                p, w, gate_before=gate_before, gate_after=gate_after,
                activation=activation, split=split)
            return sum(jnp.sum(a.astype(jnp.float32))
                       for a in jax.tree_util.tree_leaves((y, rest)))
        # with the value: the gradient alone needs no forward, its
        # residuals being the inputs
        text = _lower_for_tpu(jax.value_and_grad(loss, argnums=(0, 1)),
                              p, w).as_text()
        assert sorted(re.findall(
            r'kernel_name = "(causal_conv_[a-z]+)"', text)) == [
                "causal_conv_bwd", "causal_conv_fwd"]
        assert "stablehlo.pad" not in text

    def test_flash_backward_shards_by_batch_under_the_declared_mesh(
            self, eight_devices):
        """A pallas_call does not partition itself as the scan did: under
        the declared mesh the backward kernel runs once a batch shard, the
        key mask sharded with it."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from deeplearning4j_tpu.ops import spmd
        mesh = Mesh(np.array(eight_devices), ("data",))
        sh = NamedSharding(mesh, P("data"))
        q = jax.ShapeDtypeStruct((8, 1024, 4, 64), jnp.bfloat16, sharding=sh)
        mask = jax.ShapeDtypeStruct((8, 1024), jnp.float32, sharding=sh)

        def grads(q, k, v, mask):
            with spmd.kernel_mesh(mesh):
                return jax.grad(lambda q, k, v: jnp.sum(
                    attention_pallas.flash_attention(
                        q, k, v, mask=mask, causal=True)
                    .astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)
        text = _lower_for_tpu(grads, q, q, q, mask).as_text()
        call = next(ln for ln in text.splitlines()
                    if 'kernel_name = "flash_attn_bwd_fused"' in ln)
        operands, results = call.split(" : (")[1].split(") -> ")
        operands = _after_the_step_list(operands, 3)
        # one batch element of four heads a device; its [1, 8, T] mask
        assert operands.startswith(
            ", ".join(["tensor<4x1024x64xbf16>"] * 4)), operands
        assert operands.endswith("tensor<1x8x1024xf32>"), operands
        assert results.count("tensor<4x1024x64xbf16>") == 3, results

    def test_ring_attention_block(self):
        q = jnp.zeros((2, 1024, 8, 64), jnp.bfloat16)

        def loss(q, k, v):
            out, lse = attention_pallas.flash_attention_block(
                q, k, v, True, 0.125, False)
            return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)
        text = _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)),
                              q, q, q).as_text()
        assert "tpu_custom_call" in text

    def test_batch_sharded_operands_need_the_declared_mesh(self,
                                                           eight_devices):
        """GSPMD cannot partition a Mosaic kernel: with batch-sharded
        operands the lowering is refused unless the caller declares its
        mesh (ops/spmd.py) — what ParallelTrainer and
        BucketedForward(mesh=) do around their traces."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from deeplearning4j_tpu.ops import spmd
        mesh = Mesh(np.array(eight_devices[:4]), ("data",))
        sh = NamedSharding(mesh, P("data"))
        q = jax.ShapeDtypeStruct((8, 1024, 8, 64), jnp.bfloat16, sharding=sh)

        def attn(q, k, v):
            return attention_pallas.flash_attention(q, k, v, causal=True)

        def declared(q, k, v):
            with spmd.kernel_mesh(mesh):
                return attn(q, k, v)
        with pytest.raises(NotImplementedError, match="shard_map"):
            _lower_for_tpu(attn, q, q, q)
        assert "tpu_custom_call" in _lower_for_tpu(declared, q, q,
                                                   q).as_text()

        xz = jax.ShapeDtypeStruct(
            (16, 32, 2048), jnp.bfloat16,
            sharding=NamedSharding(mesh, P(None, "data")))
        hc = jax.ShapeDtypeStruct((32, 512), jnp.bfloat16, sharding=sh)
        wh = jnp.zeros((512, 2048), jnp.bfloat16)
        mask = jnp.ones((16, 32), jnp.float32)

        def lstm(xz, wh, h0, c0, mask):
            with spmd.kernel_mesh(mesh):
                return lstm_pallas.fused_sequence_padded(xz, wh, h0, c0,
                                                         mask=mask)
        assert "tpu_custom_call" in _lower_for_tpu(lstm, xz, wh, hc, hc,
                                                   mask).as_text()


class TestKernelsPerBatchShard:
    """ops/spmd.py numerics: under a declared mesh the kernels run once per
    batch shard and agree with the unsharded call (interpret mode)."""

    def test_flash_with_padding_mask(self, eight_devices):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from deeplearning4j_tpu.ops import spmd
        mesh = Mesh(np.array(eight_devices[:4]), ("data",))
        rs = np.random.RandomState(5)
        q, k, v = (jnp.asarray(rs.randn(8, 128, 2, 16).astype(np.float32))
                   for _ in range(3))
        mask = jnp.asarray((np.arange(128)[None, :]
                            < rs.randint(64, 129, 8)[:, None])
                           .astype(np.float32))

        def attn(q, k, v, mask):
            return attention_pallas.flash_attention(
                q, k, v, mask=mask, causal=True, block_q=128, block_k=128,
                interpret=True)

        def sharded(q, k, v, mask):
            with spmd.kernel_mesh(mesh):
                return attn(q, k, v, mask)
        sh = NamedSharding(mesh, P("data"))
        got = jax.jit(sharded, in_shardings=(sh,) * 4)(q, k, v, mask)
        assert got.sharding.spec[0] == "data"
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(attn(q, k, v, mask)),
                                   atol=1e-6)

    def test_flash_gradients_with_padding_mask(self, eight_devices):
        """The backward kernel once a batch shard, its mask sharded with
        the batch, against the same gradients with no mesh declared."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from deeplearning4j_tpu.ops import spmd
        mesh = Mesh(np.array(eight_devices[:4]), ("data",))
        rs = np.random.RandomState(28)
        q, k, v = (jnp.asarray(rs.randn(8, 128, 2, 16).astype(np.float32))
                   for _ in range(3))
        mask = jnp.asarray((np.arange(128)[None, :]
                            < rs.randint(64, 129, 8)[:, None])
                           .astype(np.float32))

        def grads(q, k, v, mask):
            return jax.grad(lambda q, k, v: jnp.sum(
                attention_pallas.flash_attention(
                    q, k, v, mask=mask, causal=True, block_q=128,
                    block_k=128, interpret=True) ** 2),
                argnums=(0, 1, 2))(q, k, v)

        def sharded(q, k, v, mask):
            with spmd.kernel_mesh(mesh):
                return grads(q, k, v, mask)
        sh = NamedSharding(mesh, P("data"))
        got = jax.jit(sharded, in_shardings=(sh,) * 4)(q, k, v, mask)
        for g, r in zip(got, grads(q, k, v, mask)):
            assert g.sharding.spec[0] == "data"
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=1e-5, atol=1e-6)

    def test_masked_peephole_lstm(self, eight_devices):
        from jax.sharding import Mesh
        from deeplearning4j_tpu.ops import spmd
        mesh = Mesh(np.array(eight_devices[:4]), ("data",))
        xz, wh, h0, c0 = _inputs(T=4, B=16, H=128, seed=31)
        rs = np.random.RandomState(31)
        wp = jnp.asarray(rs.randn(3, 128).astype(np.float32) * 0.1)
        mask = jnp.asarray((np.arange(4)[:, None]
                            < rs.randint(1, 5, 16)[None, :])
                           .astype(np.float32))

        def run(xz, wh, h0, c0, wp, mask):
            return lstm_pallas.fused_sequence_padded(
                xz, wh, h0, c0, wp=wp, mask=mask, interpret=True)

        def sharded(*a):
            with spmd.kernel_mesh(mesh):
                return run(*a)
        hs, (hT, cT) = jax.jit(sharded)(xz, wh, h0, c0, wp, mask)
        hs_r, (hT_r, cT_r) = _ref_scan_any(xz, wh, h0, c0, wp=wp, mask=mask)
        np.testing.assert_allclose(np.asarray(hs), np.asarray(hs_r),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(cT), np.asarray(cT_r),
                                   atol=1e-5)
