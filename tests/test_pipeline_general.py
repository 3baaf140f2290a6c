"""Generalized heterogeneous-stage pipeline (parallel/pipeline_general.py)
and the 1F1B schedule (parallel/pipeline.py one_f_one_b_schedule) —
VERDICT r3 #5/#6. Reference role: ParallelWrapper.java:58 wraps any Model.
Runs on the virtual 8-device CPU mesh (conftest)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn.conf.inputs import ConvolutionalType, RecurrentType
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.pipeline_general import (PipelinedNetwork,
                                                          balance_stages)

pytestmark = pytest.mark.slow


def _conv_conf():
    return NeuralNetConfig(seed=3).list(
        L.ConvolutionLayer(n_out=8, kernel=(3, 3), padding="same",
                           activation="relu"),
        L.SubsamplingLayer(kernel=(2, 2), stride=(2, 2)),
        L.ConvolutionLayer(n_out=16, kernel=(3, 3), padding="same",
                           activation="relu"),
        L.DenseLayer(n_out=32, activation="relu"),
        L.OutputLayer(n_out=5, loss="mcxent"),
        input_type=ConvolutionalType(8, 8, 1))


def _data(rs, b=8):
    x = rs.randn(b, 8, 8, 1).astype(np.float32)
    y = np.eye(5, dtype=np.float32)[rs.randint(0, 5, b)]
    return x, y


class TestGeneralPipeline:
    def test_loss_matches_sequential(self):
        conf = _conv_conf()
        net = MultiLayerNetwork(conf)
        net.init()
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("data", "stage"))
        pn = PipelinedNetwork(conf, mesh, n_microbatches=2)
        pn.init(from_params=net.params)
        rs = np.random.RandomState(0)
        x, y = _data(rs)
        l_ref, _ = net.loss_fn(net.params, net.state, jnp.asarray(x),
                               jnp.asarray(y), train=True, rng=None)
        l_pipe = pn.loss(x, y)
        assert abs(float(l_ref) - float(l_pipe)) < 2e-5

    def test_gradients_match_sequential(self):
        conf = _conv_conf()
        net = MultiLayerNetwork(conf)
        net.init()
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2,), ("stage",))
        pn = PipelinedNetwork(conf, mesh, n_microbatches=4)
        pn.init(from_params=net.params)
        rs = np.random.RandomState(1)
        x, y = _data(rs)
        g_pipe, _ = jax.grad(pn._loss_fn, has_aux=True)(
            pn.params, pn.state, jnp.asarray(x), jnp.asarray(y), None)
        unpacked = pn.unpack(g_pipe["stages"])
        _, _, g_ref = net.compute_gradients(net.params, net.state,
                                            jnp.asarray(x), jnp.asarray(y))
        for a, b in zip(unpacked, g_ref):
            for k in a:
                np.testing.assert_allclose(a[k], b[k], atol=5e-5,
                                           err_msg=k)

    def test_training_reduces_loss(self):
        conf = _conv_conf()
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("data", "stage"))
        pn = PipelinedNetwork(conf, mesh, n_microbatches=2)
        pn.init()
        rs = np.random.RandomState(2)
        x, y = _data(rs)
        l0 = float(pn.step(x, y))
        for _ in range(5):
            l = float(pn.step(x, y))
        assert l < l0

    def test_char_rnn_stack_pipelines(self):
        """The reference's signature RNN config (BASELINE #4 shape) splits
        into stages too — LSTM layers are just activation transforms."""
        conf = NeuralNetConfig(seed=4).list(
            L.LSTM(n_out=24),
            L.LSTM(n_out=24),
            L.RnnOutputLayer(n_out=7, loss="mcxent"),
            input_type=RecurrentType(6, 5))
        net = MultiLayerNetwork(conf)
        net.init()
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2,), ("stage",))
        pn = PipelinedNetwork(conf, mesh, n_microbatches=2,
                              stage_layers=[[0], [1, 2]])
        pn.init(from_params=net.params)
        rs = np.random.RandomState(5)
        x = rs.randn(4, 5, 6).astype(np.float32)
        y = np.eye(7, dtype=np.float32)[rs.randint(0, 7, (4, 5))]
        l_ref, _ = net.loss_fn(net.params, net.state, jnp.asarray(x),
                               jnp.asarray(y), train=True, rng=None)
        l_pipe = pn.loss(x, y)
        assert abs(float(l_ref) - float(l_pipe)) < 2e-5

    def test_balance_stages_contiguous_cover(self):
        conf = _conv_conf()
        groups = balance_stages(conf, 2)
        assert [i for g in groups for i in g] == list(range(5))
        assert all(g for g in groups)

    def test_moe_aux_loss_refused(self):
        """Aux-loss layers stay outside the pipelined region (their
        load-balancing term rides the activation path)."""
        conf = NeuralNetConfig(seed=1).list(
            L.MoETransformerBlock(n_out=8, n_heads=2, n_experts=2),
            L.RnnOutputLayer(n_out=3, loss="mcxent"),
            input_type=RecurrentType(8, 4))
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2,), ("stage",))
        with pytest.raises(AssertionError, match="aux loss"):
            PipelinedNetwork(conf, mesh)


class TestOneFOneB:
    def test_lm_1f1b_matches_gpipe(self):
        from deeplearning4j_tpu.parallel.pipeline import PipelineParallelLM
        devs = np.array(jax.devices()[:8]).reshape(2, 4)
        mesh = Mesh(devs, ("data", "stage"))
        kw = dict(vocab_size=50, n_layers=4, d_model=32, n_heads=2,
                  seq_len=8, mesh=mesh, n_microbatches=4)
        rs = np.random.RandomState(0)
        ids = rs.randint(0, 50, (8, 8))
        labels = rs.randint(0, 50, (8, 8))
        lm_g = PipelineParallelLM(**kw).init(jax.random.PRNGKey(1))
        lm_f = PipelineParallelLM(**kw, schedule="1f1b").init(
            jax.random.PRNGKey(1))
        lm_f.params = jax.tree_util.tree_map(
            lambda a, sh: jax.device_put(a, sh),
            jax.device_get(lm_g.params), lm_f.param_shardings)
        l_ref = lm_g.loss_reference(ids, labels)
        lg = lm_g.step(ids, labels)
        lf = lm_f.step(ids, labels)
        assert abs(float(lg) - float(l_ref)) < 2e-5
        assert abs(float(lf) - float(l_ref)) < 2e-5
        # same grads -> identical params after the same Adam step
        pg, pf = jax.device_get(lm_g.params), jax.device_get(lm_f.params)
        for a, b in zip(jax.tree_util.tree_leaves(pg),
                        jax.tree_util.tree_leaves(pf)):
            np.testing.assert_allclose(a, b, atol=1e-5)

    @pytest.mark.parametrize("shape", [(1, 2, 2, 2), (2, 2, 1, 2)])
    def test_composed_1f1b_matches_gpipe_tp_sp(self, shape):
        """Both facade shapes: tp x sp (dp=1) and dp x tp (the data-axis
        grad/loss psum with a real data axis)."""
        from deeplearning4j_tpu.parallel.composed import ComposedParallelLM
        devs = np.array(jax.devices()[:8]).reshape(*shape)
        mesh = Mesh(devs, ("data", "model", "seq", "stage"))
        kw = dict(vocab_size=50, n_layers=4, d_model=32, n_heads=4,
                  seq_len=8, mesh=mesh, n_microbatches=2)
        rs = np.random.RandomState(3)
        ids = rs.randint(0, 50, (4, 8))
        labels = rs.randint(0, 50, (4, 8))
        lm_g = ComposedParallelLM(**kw)
        lm_g.init(jax.random.PRNGKey(1))
        lm_f = ComposedParallelLM(**kw, schedule="1f1b",
                                  shard_optimizer_state=True)
        lm_f.init(jax.random.PRNGKey(1))
        lm_f.params = jax.tree_util.tree_map(
            lambda a, sh: jax.device_put(a, sh),
            jax.device_get(lm_g.params), lm_f.param_shardings)
        lg = lm_g.step(ids, labels)
        lf = lm_f.step(ids, labels)
        assert abs(float(lg) - float(lf)) < 5e-5
        pg, pf = jax.device_get(lm_g.params), jax.device_get(lm_f.params)
        for a, b in zip(jax.tree_util.tree_leaves(pg),
                        jax.tree_util.tree_leaves(pf)):
            np.testing.assert_allclose(a, b, atol=2e-5)

    def test_fg_boundary_pair_transposes(self):
        """The f/g custom-VJP pair: g backward is identity, f backward is
        psum — the pattern that makes inside-body vjp match whole-
        shard_map AD (pinned independently of the LM)."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from deeplearning4j_tpu.parallel.composed import (id_psum_bwd,
                                                          psum_id_bwd)
        mesh = Mesh(np.array(jax.devices()[:2]), ("m",))
        w = jnp.arange(4, dtype=jnp.float32).reshape(2, 2) + 1.0
        x = jnp.ones((2,), jnp.float32)

        def inner(wl, x):
            # column-parallel entry then row-parallel exit
            xe = id_psum_bwd(x, "m")
            return psum_id_bwd(wl @ xe, "m")

        def loss_outside(w):
            def plain(wl, x):
                return jax.lax.psum(wl @ x, "m")
            y = shard_map(plain, mesh=mesh, in_specs=(P("m"), P()),
                          out_specs=P(), check_vma=False)(w, x)
            return jnp.sum(y ** 2)

        def inside(w):
            def body(wl, x):
                def f(wl):
                    return jnp.sum(inner(wl, x) ** 2)
                l, vjp = jax.vjp(f, wl)
                (dw,) = vjp(jnp.ones_like(l))
                return l, dw
            return shard_map(body, mesh=mesh, in_specs=(P("m"), P()),
                             out_specs=(P(), P("m")), check_vma=False)(w, x)

        g_ref = jax.grad(loss_outside)(w)
        _, g_in = jax.jit(inside)(w)
        np.testing.assert_allclose(np.asarray(g_in), np.asarray(g_ref),
                                   atol=1e-5)


class TestPipelineCheckpointInterop:
    def test_pipeline_trained_params_export_to_zip(self, tmp_path):
        """A pipeline-trained network exports through the STANDARD
        checkpoint path: unpack() -> MultiLayerNetwork -> save_model ->
        load_model, predictions identical (reference contract:
        ModelSerializer round-trips any trained Model)."""
        from deeplearning4j_tpu.utils.serialization import (load_model,
                                                            save_model)
        conf = _conv_conf()
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2,), ("stage",))
        pn = PipelinedNetwork(conf, mesh, n_microbatches=2)
        pn.init()
        rs = np.random.RandomState(7)
        x, y = _data(rs)
        for _ in range(3):
            pn.step(x, y)
        net = MultiLayerNetwork(conf)
        net.init()
        net.params = pn.unpack()
        # the unpacked params must BE the trained params: the sequential
        # loss on them equals the pipeline's own loss
        l_seq, _ = net.loss_fn(net.params, net.state, jnp.asarray(x),
                               jnp.asarray(y), train=True, rng=None)
        l_pipe = pn.loss(x, y)
        assert abs(float(l_seq) - float(l_pipe)) < 2e-5
        p = str(tmp_path / "pipelined.zip")
        save_model(net, p)
        net2 = load_model(p)
        out1 = net.output(x)
        out2 = net2.output(x)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   atol=1e-6)


class TestGeneralPipeline1F1B:
    @pytest.mark.parametrize("shape,axes", [((2,), ("stage",)),
                                            ((2, 2), ("data", "stage"))])
    def test_general_1f1b_matches_gpipe(self, shape, axes):
        """schedule='1f1b' on the heterogeneous pipeline: identical loss
        and post-Adam params to the GPipe path (explicit-VJP schedule
        changes order and memory, never math)."""
        conf = _conv_conf()
        devs = np.array(jax.devices()[:int(np.prod(shape))]).reshape(shape)
        mesh = Mesh(devs, axes)
        pg = PipelinedNetwork(conf, mesh, n_microbatches=2).init()
        pf = PipelinedNetwork(conf, mesh, n_microbatches=2,
                              schedule="1f1b")
        pf.init(from_params=pg.unpack())
        rs = np.random.RandomState(0)
        x, y = _data(rs)
        lg = float(pg.step(x, y))
        lf = float(pf.step(x, y))
        assert abs(lg - lf) < 5e-5
        np.testing.assert_allclose(
            jax.device_get(pg.params["stages"]),
            jax.device_get(pf.params["stages"]), atol=2e-5)

    def test_1f1b_with_l2_penalty(self):
        """Regularization grads add outside the schedule; loss still
        matches the gpipe path (which carries penalties in-loss)."""
        conf = NeuralNetConfig(seed=5, l2=1e-3).list(
            L.DenseLayer(n_out=16, activation="relu"),
            L.OutputLayer(n_out=3, loss="mcxent"),
            input_type=ConvolutionalType(4, 4, 1))
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2,), ("stage",))
        pg = PipelinedNetwork(conf, mesh, n_microbatches=2).init()
        pf = PipelinedNetwork(conf, mesh, n_microbatches=2,
                              schedule="1f1b")
        pf.init(from_params=pg.unpack())
        rs = np.random.RandomState(1)
        x = rs.randn(4, 4, 4, 1).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 4)]
        lg = float(pg.step(x, y))
        lf = float(pf.step(x, y))
        assert abs(lg - lf) < 5e-5
        np.testing.assert_allclose(
            jax.device_get(pg.params["stages"]),
            jax.device_get(pf.params["stages"]), atol=2e-5)


class TestStatefulPipeline:
    """VERDICT r4 #3: BN running stats as per-stage carried state +
    per-stage rng fold for dropout — the flagship conv-BN family staged."""

    def _resnet_conf(self):
        from deeplearning4j_tpu.models.resnet import resnet50_mln
        return resnet50_mln(height=16, width=16, channels=3, n_classes=5,
                            stages=[(4, 2, (1, 1)), (8, 2, (2, 2))],
                            stem_filters=4, seed=9)

    def _seq_microbatch_run(self, net, x, y, n_micro, rng=None):
        """Sequential per-microbatch reference: same microbatch split,
        same per-microbatch keys, state threaded mb k -> k+1."""
        b = x.shape[0]
        mb = b // n_micro
        state, losses = net.state, []
        for k in range(n_micro):
            rk = None if rng is None else jax.random.fold_in(rng, k)
            l, (state, _) = net.loss_fn(
                net.params, state, jnp.asarray(x[k * mb:(k + 1) * mb]),
                jnp.asarray(y[k * mb:(k + 1) * mb]), train=True, rng=rk)
            losses.append(float(l))
        return float(np.mean(losses)), state

    def test_reduced_resnet50_loss_and_state_pin(self):
        """Pipelined reduced ResNet50 (BN in every bottleneck): loss AND
        final running stats pinned to a sequential per-microbatch run on
        the same params."""
        conf = self._resnet_conf()
        net = MultiLayerNetwork(conf)
        net.init()
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(4,), ("stage",))
        pn = PipelinedNetwork(conf, mesh, n_microbatches=2)
        pn.init(from_params=net.params, from_state=net.state)
        rs = np.random.RandomState(0)
        x = rs.randn(8, 16, 16, 3).astype(np.float32)
        y = np.eye(5, dtype=np.float32)[rs.randint(0, 5, 8)]
        l_ref, st_ref = self._seq_microbatch_run(net, x, y, 2)
        l_pipe, new_states = pn._loss_fn(pn.params, pn.state,
                                         jnp.asarray(x), jnp.asarray(y),
                                         None)
        assert abs(float(l_pipe) - l_ref) < 2e-5
        unpacked = pn.unpack_state(new_states["stages"])
        for a, b in zip(unpacked, st_ref):
            assert set(a) == set(b)
            for k in a:
                va = a[k] if not isinstance(a[k], dict) else a[k]
                for leaf_a, leaf_b in zip(
                        jax.tree_util.tree_leaves(a[k]),
                        jax.tree_util.tree_leaves(b[k])):
                    np.testing.assert_allclose(np.asarray(leaf_a),
                                               np.asarray(leaf_b),
                                               atol=1e-5, err_msg=k)

    def test_dropout_pipeline_loss_pin(self):
        """Dropout inside pipelined stages: the stage branches replicate
        MultiLayerNetwork.apply_fn's key-split chain, so the loss with a
        shared step key equals the sequential per-microbatch run with the
        same per-microbatch keys (bit-identical masks)."""
        conf = NeuralNetConfig(seed=5).list(
            L.ConvolutionLayer(n_out=6, kernel=(3, 3), padding="same",
                               activation="relu"),
            L.BatchNormalization(),
            L.DenseLayer(n_out=24, activation="relu", dropout=0.4),
            L.DenseLayer(n_out=16, activation="relu"),
            L.OutputLayer(n_out=5, loss="mcxent", dropout=0.3),
            input_type=ConvolutionalType(6, 6, 2))
        net = MultiLayerNetwork(conf)
        net.init()
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2,), ("stage",))
        pn = PipelinedNetwork(conf, mesh, n_microbatches=2)
        pn.init(from_params=net.params, from_state=net.state)
        rs = np.random.RandomState(3)
        x = rs.randn(8, 6, 6, 2).astype(np.float32)
        y = np.eye(5, dtype=np.float32)[rs.randint(0, 5, 8)]
        key = jax.random.PRNGKey(77)
        l_ref, _ = self._seq_microbatch_run(net, x, y, 2, rng=key)
        l_pipe, _ = pn._loss_fn(pn.params, pn.state, jnp.asarray(x),
                                jnp.asarray(y), key)
        assert abs(float(l_pipe) - l_ref) < 2e-5
        # and WITHOUT a key the losses differ (dropout really fired)
        l_nodrop, _ = pn._loss_fn(pn.params, pn.state, jnp.asarray(x),
                                  jnp.asarray(y), None)
        assert abs(float(l_nodrop) - float(l_pipe)) > 1e-6

    def test_resnet_training_reduces_loss_and_updates_stats(self):
        conf = self._resnet_conf()
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("data", "stage"))
        pn = PipelinedNetwork(conf, mesh, n_microbatches=2)
        pn.init()
        st0 = jax.device_get(pn.state["stages"]).copy()
        rs = np.random.RandomState(2)
        x = rs.randn(8, 16, 16, 3).astype(np.float32)
        y = np.eye(5, dtype=np.float32)[rs.randint(0, 5, 8)]
        l0 = float(pn.step(x, y))
        for _ in range(5):
            l = float(pn.step(x, y))
        assert l < l0
        st1 = jax.device_get(pn.state["stages"])
        assert not np.allclose(st0, st1)  # running stats actually moved

    def test_bn_dropout_1f1b_matches_gpipe(self):
        """The stateful+dropout net under BOTH schedules: identical loss,
        post-Adam params, AND final BN running stats (1F1B recompute is
        exact for state-independent forwards + deterministic keys)."""
        import dataclasses
        conf = self._resnet_conf()
        conf = dataclasses.replace(
            conf, layers=conf.layers[:-1] + (
                dataclasses.replace(conf.layers[-1], dropout=0.25),))
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2,), ("stage",))
        pg = PipelinedNetwork(conf, mesh, n_microbatches=2).init()
        pf = PipelinedNetwork(conf, mesh, n_microbatches=2,
                              schedule="1f1b")
        pf.init(from_params=pg.unpack(), from_state=pg.unpack_state())
        rs = np.random.RandomState(0)
        x = rs.randn(8, 16, 16, 3).astype(np.float32)
        y = np.eye(5, dtype=np.float32)[rs.randint(0, 5, 8)]
        lg = float(pg.step(x, y))
        lf = float(pf.step(x, y))
        assert abs(lg - lf) < 5e-5, (lg, lf)
        np.testing.assert_allclose(
            jax.device_get(pg.params["stages"]),
            jax.device_get(pf.params["stages"]), atol=2e-5)
        np.testing.assert_allclose(
            jax.device_get(pg.state["stages"]),
            jax.device_get(pf.state["stages"]), atol=1e-5)

    @pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
    def test_masked_lstm_stack_loss_pin(self, schedule):
        """Masked sequence batches stage under BOTH schedules: the mask
        reaches the LSTM layers and the output loss, pinned against the
        sequential per-microbatch run with the same mask slices."""
        conf = NeuralNetConfig(seed=6).list(
            L.LSTM(n_out=16),
            L.LSTM(n_out=16),
            L.RnnOutputLayer(n_out=5, loss="mcxent"),
            input_type=RecurrentType(4, 6))
        net = MultiLayerNetwork(conf)
        net.init()
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2,), ("stage",))
        pn = PipelinedNetwork(conf, mesh, n_microbatches=2,
                              stage_layers=[[0], [1, 2]],
                              schedule=schedule)
        pn.init(from_params=net.params, from_state=net.state)
        rs = np.random.RandomState(8)
        x = rs.randn(8, 6, 4).astype(np.float32)
        y = np.eye(5, dtype=np.float32)[rs.randint(0, 5, (8, 6))]
        mask = (rs.rand(8, 6) > 0.3).astype(np.float32)
        mask[:, 0] = 1.0  # no fully-masked leading step
        # BN-free stack: the pipelined forward equals the full-batch
        # forward, so the exact reference is the full-batch masked loss
        # (mask counts differ per microbatch — the schedules reweight
        # each microbatch's masked mean by its local count)
        l, _ = net.loss_fn(net.params, net.state, jnp.asarray(x),
                           jnp.asarray(y), train=True,
                           mask=jnp.asarray(mask))
        l_ref = float(l)
        if schedule == "gpipe":
            l_pipe, _ = pn._loss_fn(pn.params, pn.state, jnp.asarray(x),
                                    jnp.asarray(y), None,
                                    jnp.asarray(mask))
        else:
            l_pipe, _, _ = pn._loss_and_grads_1f1b(
                pn.params, pn.state, jnp.asarray(x), jnp.asarray(y),
                None, jnp.asarray(mask))
        assert abs(float(l_pipe) - l_ref) < 2e-5, (float(l_pipe), l_ref)
        # and the mask matters: unmasked loss differs
        l_nomask = float(pn.loss(x, y))
        assert abs(l_nomask - l_ref) > 1e-6
        # full training step with a mask runs
        l_step = float(pn.step(x, y, mask=mask))
        assert np.isfinite(l_step)

    def test_stateful_sharded_checkpoint_roundtrip(self, tmp_path):
        """BN running stats + the dropout step key survive the orbax
        trainer lifecycle (utils/sharded_checkpoint picks up .state and
        ._rng automatically)."""
        from deeplearning4j_tpu.utils.sharded_checkpoint import (
            restore_trainer, save_trainer)
        conf = self._resnet_conf()
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2,), ("stage",))
        pn = PipelinedNetwork(conf, mesh, n_microbatches=2).init()
        rs = np.random.RandomState(4)
        x = rs.randn(4, 16, 16, 3).astype(np.float32)
        y = np.eye(5, dtype=np.float32)[rs.randint(0, 5, 4)]
        for _ in range(2):
            pn.step(x, y)
        path = str(tmp_path / "bn_pipe_ckpt")
        save_trainer(path, pn)
        st_saved = jax.device_get(pn.state["stages"]).copy()
        l_next = float(pn.step(x, y))
        pn2 = PipelinedNetwork(conf, mesh, n_microbatches=2).init()
        restore_trainer(path, pn2)
        np.testing.assert_allclose(jax.device_get(pn2.state["stages"]),
                                   st_saved)
        l_resume = float(pn2.step(x, y))
        assert abs(l_resume - l_next) < 1e-5


class TestPipelineShardedCheckpoint:
    def test_sharded_checkpoint_resume(self, tmp_path):
        """PipelinedNetwork through the orbax sharded-checkpoint
        lifecycle (utils/sharded_checkpoint): save mid-training, restore
        into a fresh instance with the stage shardings preserved, and the
        next step matches an uninterrupted run."""
        from deeplearning4j_tpu.utils.sharded_checkpoint import (
            restore_trainer, save_trainer)
        conf = _conv_conf()
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2,), ("stage",))
        pn = PipelinedNetwork(conf, mesh, n_microbatches=2).init()
        rs = np.random.RandomState(11)
        x, y = _data(rs)
        for _ in range(2):
            pn.step(x, y)
        path = str(tmp_path / "pipe_ckpt")
        save_trainer(path, pn)
        l_next = float(pn.step(x, y))  # the uninterrupted third step

        pn2 = PipelinedNetwork(conf, mesh, n_microbatches=2).init()
        restore_trainer(path, pn2)
        assert pn2.iteration == 2
        # restored params keep the stage sharding
        assert pn2.params["stages"].sharding.is_equivalent_to(
            pn.params["stages"].sharding, pn.params["stages"].ndim)
        l_resume = float(pn2.step(x, y))
        assert abs(l_resume - l_next) < 1e-5


class TestPipelinedGraph:
    """PipelinedGraph: the flagship ComputationGraph itself staged
    (reference: ParallelWrapper wraps any Model — CG included). Skip
    connections of any span ride the boundary buffers."""

    def _resnet_conf(self):
        from deeplearning4j_tpu.models.resnet import resnet50
        return resnet50(height=16, width=16, channels=3, n_classes=4,
                        seed=13)

    def _data(self, rs, b=8):
        x = rs.randn(b, 16, 16, 3).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rs.randint(0, 4, b)]
        return x, y

    def test_resnet50_graph_loss_and_state_pin(self):
        """The REAL (reduced-size) ResNet50 ComputationGraph — 141
        vertices, BN in every bottleneck, ElementWise-add shortcuts —
        staged over 4 devices: loss AND final BN stats pinned to the
        sequential per-microbatch run."""
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.parallel.pipeline_general import \
            PipelinedGraph
        conf = self._resnet_conf()
        net = ComputationGraph(conf)
        net.init()
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(4,), ("stage",))
        pg = PipelinedGraph(conf, mesh, n_microbatches=2)
        pg.init(from_params=net.params, from_state=net.state)
        rs = np.random.RandomState(0)
        x, y = self._data(rs)
        state, losses = net.state, []
        for k in range(2):
            l, (state, _) = net.loss_fn(net.params, state,
                                        x[k * 4:(k + 1) * 4],
                                        y[k * 4:(k + 1) * 4], train=True)
            losses.append(float(l))
        l_ref = float(np.mean(losses))
        l_pipe, new_states = pg._loss_fn(pg.params, pg.state,
                                         jnp.asarray(x), jnp.asarray(y))
        assert abs(float(l_pipe) - l_ref) < 2e-5
        unpacked = pg.unpack_state(new_states["stages"])
        for name, st_ref in state.items():
            for leaf_a, leaf_b in zip(
                    jax.tree_util.tree_leaves(unpacked[name]),
                    jax.tree_util.tree_leaves(st_ref)):
                np.testing.assert_allclose(np.asarray(leaf_a),
                                           np.asarray(leaf_b),
                                           atol=1e-5, err_msg=name)

    def test_training_reduces_loss_data_stage_mesh(self):
        from deeplearning4j_tpu.parallel.pipeline_general import \
            PipelinedGraph
        conf = self._resnet_conf()
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                    ("data", "stage"))
        pg = PipelinedGraph(conf, mesh, n_microbatches=2).init()
        rs = np.random.RandomState(2)
        x, y = self._data(rs)
        st0 = jax.device_get(pg.state["stages"]).copy()
        l0 = float(pg.step(x, y))
        for _ in range(4):
            l = float(pg.step(x, y))
        assert l < l0
        assert not np.allclose(st0, jax.device_get(pg.state["stages"]))

    def test_long_skip_across_stage_boundaries(self):
        """A skip edge spanning three stages forwards through the
        intermediate boundary buffers; loss pinned to the sequential
        graph."""
        from deeplearning4j_tpu.nn.graph import (ComputationGraph,
                                                 ElementWiseVertex,
                                                 GraphBuilder)
        from deeplearning4j_tpu.nn.conf.inputs import FeedForwardType
        from deeplearning4j_tpu.parallel.pipeline_general import \
            PipelinedGraph
        g = GraphBuilder(seed=4)
        g.add_inputs("in")
        g.set_input_types(FeedForwardType(12))
        g.add_layer("d1", L.DenseLayer(n_out=12, activation="relu"), "in")
        g.add_layer("d2", L.DenseLayer(n_out=12, activation="relu"), "d1")
        g.add_layer("d3", L.DenseLayer(n_out=12, activation="relu"), "d2")
        g.add_layer("d4", L.DenseLayer(n_out=12, activation="relu"), "d3")
        # skip from d1 all the way to the last stage
        g.add_vertex("add", ElementWiseVertex(op="add"), "d4", "d1")
        g.add_layer("out", L.OutputLayer(n_out=3, loss="mcxent"), "add")
        g.set_outputs("out")
        conf = g.build()
        net = ComputationGraph(conf)
        net.init()
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(4,), ("stage",))
        pg = PipelinedGraph(
            conf, mesh, n_microbatches=2,
            stage_vertices=[["d1"], ["d2"], ["d3"], ["d4", "add", "out"]])
        # d1's output must be live across boundaries 1, 2, 3
        assert all("d1" in b for b in pg._boundaries[1:4])
        pg.init(from_params=net.params, from_state=net.state)
        rs = np.random.RandomState(5)
        x = rs.randn(8, 12).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 8)]
        l_ref, _ = net.loss_fn(net.params, net.state, x, y, train=True)
        l_pipe = pg.loss(x, y)
        assert abs(float(l_ref) - float(l_pipe)) < 2e-5

    def test_unpack_exports_to_sequential_graph(self):
        """Pipeline-trained params export into a plain ComputationGraph
        (the ModelSerializer-roundtrip interop contract).

        The export contract is pinned EXACTLY: repack(unpack()) is
        bit-identical to the trained slab, and a fresh pipeline built
        from the export reproduces the loss bit-for-bit. The sequential
        cross-check carries a loose tolerance by necessity, not slack:
        on post-step params this tiny reduced ResNet's 50-BN f32 forward
        is chaotically conditioned — jitting the IDENTICAL eager vertex
        walk moves the logits by up to 7e-3 (measured; the CG's own
        f32-vs-f64 loss gap is ~0.07 after an Adam step), so eager-CG vs
        jitted-pipeline can never pin tighter than the conditioning. The
        exact forward pin lives in the init-params test above (6e-8)."""
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.parallel.pipeline_general import \
            PipelinedGraph
        conf = self._resnet_conf()
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(4,), ("stage",))
        pg = PipelinedGraph(conf, mesh, n_microbatches=2).init()
        rs = np.random.RandomState(7)
        x, y = self._data(rs)
        for _ in range(2):
            pg.step(x, y)
        up = pg.unpack()
        ust = pg.unpack_state()
        # exact export contract
        np.testing.assert_array_equal(
            jax.device_get(pg._pack(up)),
            jax.device_get(pg.params["stages"]))
        pg2 = PipelinedGraph(conf, mesh, n_microbatches=2)
        pg2.init(from_params=up, from_state=ust)
        l_pipe, _ = pg._loss_fn(pg.params, pg.state, jnp.asarray(x),
                                jnp.asarray(y))
        l_pipe2, _ = pg2._loss_fn(pg2.params, pg2.state, jnp.asarray(x),
                                  jnp.asarray(y))
        assert float(l_pipe) == float(l_pipe2)
        # sequential cross-check at conditioning-level tolerance
        net = ComputationGraph(conf)
        net.init()
        net.params = up
        net.state = ust
        state, losses = net.state, []
        for k in range(2):
            l, (state, _) = net.loss_fn(net.params, state,
                                        x[k * 4:(k + 1) * 4],
                                        y[k * 4:(k + 1) * 4], train=True)
            losses.append(float(l))
        assert abs(float(np.mean(losses)) - float(l_pipe)) < 0.05

    def test_refuses_unsupported(self):
        from deeplearning4j_tpu.nn.graph import GraphBuilder
        from deeplearning4j_tpu.nn.conf.inputs import FeedForwardType
        from deeplearning4j_tpu.parallel.pipeline_general import \
            PipelinedGraph
        g = GraphBuilder(seed=1)
        g.add_inputs("in")
        g.set_input_types(FeedForwardType(4))
        g.add_layer("d", L.DenseLayer(n_out=4, dropout=0.5), "in")
        g.add_layer("out", L.OutputLayer(n_out=2, loss="mcxent"), "d")
        g.set_outputs("out")
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2,), ("stage",))
        with pytest.raises(AssertionError, match="dropout"):
            PipelinedGraph(g.build(), mesh)
        g2 = GraphBuilder(seed=1, gradient_normalization="clip_l2")
        g2.add_inputs("in")
        g2.set_input_types(FeedForwardType(4))
        g2.add_layer("d", L.DenseLayer(n_out=4), "in")
        g2.add_layer("out", L.OutputLayer(n_out=2, loss="mcxent"), "d")
        g2.set_outputs("out")
        with pytest.raises(AssertionError, match="gradient normalization"):
            PipelinedGraph(g2.build(), mesh)

    @pytest.mark.parametrize("shape,axes", [((4,), ("stage",)),
                                            ((2, 2), ("data", "stage"))])
    def test_graph_1f1b_matches_gpipe(self, shape, axes):
        """The ResNet50 graph under BOTH schedules: identical loss,
        post-update params, and final BN running stats (incl. the
        data-axis grad psum / stats pmean path)."""
        from deeplearning4j_tpu.parallel.pipeline_general import \
            PipelinedGraph
        conf = self._resnet_conf()
        mesh = Mesh(np.array(jax.devices()[:int(np.prod(shape))])
                    .reshape(shape), axes)
        pgp = PipelinedGraph(conf, mesh, n_microbatches=2).init()
        pf = PipelinedGraph(conf, mesh, n_microbatches=2,
                            schedule="1f1b")
        pf.init(from_params=pgp.unpack(), from_state=pgp.unpack_state())
        rs = np.random.RandomState(3)
        x, y = self._data(rs)
        lg = float(pgp.step(x, y))
        lf = float(pf.step(x, y))
        assert abs(lg - lf) < 5e-5, (lg, lf)
        np.testing.assert_allclose(
            jax.device_get(pgp.params["stages"]),
            jax.device_get(pf.params["stages"]), atol=2e-5)
        np.testing.assert_allclose(
            jax.device_get(pgp.state["stages"]),
            jax.device_get(pf.state["stages"]), atol=1e-5)

    def test_graph_sharded_checkpoint_roundtrip(self, tmp_path):
        """PipelinedGraph through the orbax trainer lifecycle: BN slab +
        params + opt state + iteration restore, next step matches the
        uninterrupted run."""
        from deeplearning4j_tpu.utils.sharded_checkpoint import (
            restore_trainer, save_trainer)
        from deeplearning4j_tpu.parallel.pipeline_general import \
            PipelinedGraph
        conf = self._resnet_conf()
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(4,), ("stage",))
        pg = PipelinedGraph(conf, mesh, n_microbatches=2).init()
        rs = np.random.RandomState(11)
        x, y = self._data(rs, b=4)
        for _ in range(2):
            pg.step(x, y)
        path = str(tmp_path / "graph_pipe_ckpt")
        save_trainer(path, pg)
        st_saved = jax.device_get(pg.state["stages"]).copy()
        l_next = float(pg.step(x, y))
        pg2 = PipelinedGraph(conf, mesh, n_microbatches=2).init()
        restore_trainer(path, pg2)
        assert pg2.iteration == 2
        np.testing.assert_allclose(jax.device_get(pg2.state["stages"]),
                                   st_saved)
        l_resume = float(pg2.step(x, y))
        assert abs(l_resume - l_next) < 1e-5
