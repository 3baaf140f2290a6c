"""ZeRO across the stack (ISSUE 10; Xu et al. 2020, arxiv 2004.13336):
the cross-replica sharded weight update as the ParallelTrainer DEFAULT,
the FSDP parameter-sharding tier, the fused K-step engine carrying the
sharded opt state, the distributed masters' sharded updater state, and
every layout's checkpoint round-trip — with the collectives INSPECTED in
the lowered HLO, not assumed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn import updaters as U
from deeplearning4j_tpu.nn.conf import inputs as I
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel import (MeshSpec, ParallelTrainer,
                                         make_mesh)


def _net(seed=6, n_in=8, hidden=16, n_out=4):
    conf = NeuralNetConfig(seed=seed, updater=U.Adam(learning_rate=0.01)) \
        .list(L.DenseLayer(n_out=hidden, activation="tanh"),
              L.OutputLayer(n_out=n_out, loss="mcxent"),
              input_type=I.FeedForwardType(n_in))
    return MultiLayerNetwork(conf)


def _data(n=16, n_in=8, n_out=4, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.rand(n, n_in).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[rs.randint(0, n_out, n)]
    return x, y


def _trainer(mode, mesh, seed=6, **kw):
    return ParallelTrainer(
        _net(seed=seed), mesh,
        shard_optimizer_state=(mode != "replicated"),
        shard_params="fsdp" if mode == "fsdp" else None, **kw).init()


def _assert_same_math(actual, desired):
    """The sharded layouts are re-expressions of the same math, pinned to
    1e-6 relative rather than to the bit. They were bit-exact under jax
    0.4.37; under jax 0.9.0 the XLA:CPU backend compiles the elementwise
    Adam update differently for a 1/8 shard than for the full tensor: with
    bit-identical gradients (``v`` still agrees) the first moment
    ``b1*m + (1-b1)*g`` comes out one f32 ulp apart at the second step,
    and the parameters follow (max abs 2.98e-8, max relative 4.2e-7 after
    five steps). The gradient collectives are not the cause — all-reduce
    and reduce-scatter + all-gather of the same shards agree to the bit
    on this backend."""
    np.testing.assert_allclose(np.asarray(actual), np.asarray(desired),
                               rtol=1e-6, atol=1e-7)


def _stream_net(seed=6, n_in=8, hidden=64, n_out=4, depth=4):
    """A net WITH a homogeneous trunk: entry Dense(n_in->hidden) +
    ``depth`` identical Dense(hidden->hidden) blocks + output head —
    the stacked-slab shape the fsdp_stream tier scans."""
    conf = NeuralNetConfig(seed=seed, updater=U.Adam(learning_rate=0.01)) \
        .list(L.DenseLayer(n_out=hidden, activation="tanh"),
              *[L.DenseLayer(n_out=hidden, activation="tanh")
                for _ in range(depth)],
              L.OutputLayer(n_out=n_out, loss="mcxent"),
              input_type=I.FeedForwardType(n_in))
    return MultiLayerNetwork(conf)


def _stream_trainer(mode, mesh, seed=6, **kw):
    return ParallelTrainer(
        _stream_net(seed=seed), mesh,
        shard_optimizer_state=(mode != "replicated"),
        shard_params=(mode if mode in ("fsdp", "fsdp_stream") else None),
        **kw).init()


class TestZeroDefaults:
    """shard_optimizer_state defaults ON, layout derived FROM the param
    shardings (mesh.zero1_sharding — the composed.py discipline, now one
    shared definition)."""

    def test_default_trainer_shards_opt_state(self, eight_devices):
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        tr = ParallelTrainer(_net(), mesh).init()
        assert tr.shard_optimizer_state
        m = tr.opt_state["m"][0]["W"]  # Adam m of the [8,16] dense W
        assert m.sharding.spec[0] == "data"
        assert m.addressable_shards[0].data.shape[0] * 8 == m.shape[0]
        # params stay replicated (ZeRO-1, not FSDP)
        assert tr.params[0]["W"].sharding.is_fully_replicated

    def test_tp_moments_follow_param_shardings(self, eight_devices):
        """Satellite: a tensor-parallel run's Adam moments keep the
        'model' axes of their param and only gain 'data' on top — the
        old first-divisible-axis rule resharded column-sharded moments
        against their param every step."""
        mesh = make_mesh(MeshSpec(data=4, model=2), devices=eight_devices)
        tr = ParallelTrainer(_net(), mesh, tensor_parallel=True).init()
        w = tr.params[0]["W"]          # [8,16] column-sharded
        m = tr.opt_state["m"][0]["W"]
        assert w.sharding.spec[-1] == "model"
        assert m.sharding.spec[-1] == "model"   # never resharded
        assert m.sharding.spec[0] == "data"     # ZeRO extension
        # training still descends and params stay in the compute layout
        x, y = _data()
        l0 = float(tr.step(x, y))
        float(tr.step(x, y))
        assert np.isfinite(l0)
        assert tr.params[0]["W"].sharding.spec[-1] == "model"

    def test_mask_is_data_sharded(self, eight_devices):
        """Satellite: the step's mask input shards over 'data' with its
        batch (the in_shardings entry was None — masked runs replicated
        the mask to every device per dispatch)."""
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        tr = ParallelTrainer(_net(), mesh).init()
        x, y = _data()
        mask = np.ones((16,), np.float32)
        loss = tr.step(x, y, mask=mask)
        assert np.isfinite(float(loss))
        compiled = tr._step_fn.lower(
            tr.params, tr.state, tr.opt_state, jnp.asarray(x),
            jnp.asarray(y), 0, tr._rng, jnp.asarray(mask)).compile()
        args_sh, _ = compiled.input_shardings
        mask_sh = args_sh[-1]
        assert not mask_sh.is_fully_replicated
        assert mask_sh.spec[0] == "data"

    def test_zero1_falls_back_to_a_later_divisible_dim(self,
                                                       eight_devices):
        """An embedding-table-like leaf ([4097, 512]: dim 0 indivisible)
        must not silently replicate its moments — the extension falls
        through to the first divisible dim (the pre-port
        _opt_leaf_sharding behavior, kept under the derived-from-param-
        shardings rule)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from deeplearning4j_tpu.parallel import mesh as _mesh
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        repl = NamedSharding(mesh, P())
        leaf = jax.ShapeDtypeStruct((4097, 512), jnp.float32)
        got = _mesh.zero1_sharding(mesh, repl, leaf)
        assert got.spec == P(None, "data")
        # no divisible dim at all -> unchanged param sharding
        odd = jax.ShapeDtypeStruct((3, 5), jnp.float32)
        assert _mesh.zero1_sharding(mesh, repl, odd) == repl
        # an already-'data'-sharded spec is left alone
        dsh = NamedSharding(mesh, P("data", None))
        assert _mesh.zero1_sharding(
            mesh, dsh, jax.ShapeDtypeStruct((16, 16), jnp.float32)) is dsh

    def test_graph_net_single_tree_updater_state_shards(self,
                                                        eight_devices):
        """A ComputationGraph's params tree is itself a dict (keyed by
        vertex), so a params-shaped updater state (Nesterovs momenta —
        not Adam's {m,v} wrapper) must take the zero1 layout WHOLE, not
        fall into the per-entry dict fan-out and silently replicate."""
        from deeplearning4j_tpu.nn.graph import ComputationGraph, \
            GraphBuilder
        b = GraphBuilder(updater=U.Nesterovs(learning_rate=0.01), seed=5)
        b.add_inputs("in")
        b.set_input_types(I.FeedForwardType(8))
        b.add_layer("h", L.DenseLayer(n_out=16, activation="tanh"), "in")
        b.add_layer("out", L.OutputLayer(n_out=8, loss="mcxent"), "h")
        b.set_outputs("out")
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        tr = ParallelTrainer(ComputationGraph(b.build()), mesh).init()
        mom = tr.opt_state["h"]["W"]   # Nesterovs momentum of [8,16] W
        assert mom.sharding.spec[0] == "data"
        x, y = _data(n_out=8)
        assert np.isfinite(float(tr.step(x, y)))

    def test_stateless_updater_skips_the_constrained_step(self,
                                                          eight_devices):
        """Sgd has state=() — nothing to shard, so the default must NOT
        pay the reduce-scatter/all-gather machinery (pure overhead for
        zero saved bytes). FSDP still uses the constrained step: the
        params themselves are sharded."""
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        conf = NeuralNetConfig(seed=6, updater=U.Sgd(learning_rate=0.1)) \
            .list(L.DenseLayer(n_out=16, activation="tanh"),
                  L.OutputLayer(n_out=4, loss="mcxent"),
                  input_type=I.FeedForwardType(8))
        tr = ParallelTrainer(MultiLayerNetwork(conf), mesh).init()
        assert not tr._zero_step_active
        x, y = _data()
        first_plain = np.asarray(tr.step(x, y))
        assert np.isfinite(float(first_plain))
        conf2 = NeuralNetConfig(seed=6, updater=U.Sgd(learning_rate=0.1)) \
            .list(L.DenseLayer(n_out=16, activation="tanh"),
                  L.OutputLayer(n_out=4, loss="mcxent"),
                  input_type=I.FeedForwardType(8))
        tf = ParallelTrainer(MultiLayerNetwork(conf2), mesh,
                             shard_params="fsdp").init()
        assert tf._zero_step_active
        assert tf.params[0]["W"].sharding.spec[0] == "data"
        np.testing.assert_array_equal(np.asarray(tf.step(x, y)),
                                      first_plain)

    def test_fused_base_step_rejects_with_health(self):
        from deeplearning4j_tpu.nn import fused as _fused
        net = _net()
        net.init()
        with pytest.raises(ValueError, match="with_health"):
            _fused.make_train_steps(net, 2, with_health=True,
                                    base_step=lambda *a: a)

    def test_bad_shard_params_rejected(self):
        with pytest.raises(ValueError, match="fsdp"):
            ParallelTrainer(_net(), make_mesh(MeshSpec(data=8, model=1)),
                            shard_params="zero9")


class TestZeroParity:
    """The layouts are re-expressions of the same math (see
    ``_assert_same_math`` for how tightly that is pinned, and why)."""

    def test_zero1_and_fsdp_bit_exact_vs_replicated(self, eight_devices):
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        x, y = _data()
        ts = {m: _trainer(m, mesh) for m in ("replicated", "zero1", "fsdp")}
        for _ in range(5):
            losses = {m: float(t.step(x, y)) for m, t in ts.items()}
        _assert_same_math(losses["zero1"], losses["replicated"])
        _assert_same_math(losses["fsdp"], losses["replicated"])
        w_ref = np.asarray(ts["replicated"].params[0]["W"])
        for m in ("zero1", "fsdp"):
            _assert_same_math(ts[m].params[0]["W"], w_ref)

    def test_fused_k4_zero_bit_exact_vs_k1_replicated(self, eight_devices):
        """Tentpole (b): the fused lax.scan engine carries the SHARDED
        opt state through all K steps bit-exactly — K=4 + ZeRO (and
        FSDP) equals K=1 replicated to the last bit."""
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        x, y = _data(n=64)
        ref = _trainer("replicated", mesh)
        ref.fit(x, y, batch_size=16, epochs=2)           # K=1 replicated
        w_ref = np.asarray(ref.params[0]["W"])
        for mode in ("zero1", "fsdp"):
            tr = _trainer(mode, mesh)
            tr.fit(x, y, batch_size=16, epochs=2, steps_per_dispatch=4)
            _assert_same_math(tr.params[0]["W"], w_ref)
            # the carried opt state is still in the sharded layout
            m = tr.opt_state["m"][0]["W"]
            assert m.sharding.spec[0] == "data"
            assert tr.iteration == ref.iteration


class TestFSDP:
    """shard_params="fsdp" (ZeRO-3): params STORED P('data') between
    steps, gathered inside the step."""

    def test_params_stored_sharded(self, eight_devices):
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        tr = _trainer("fsdp", mesh)
        x, y = _data()
        tr.step(x, y)
        w = tr.params[0]["W"]
        assert w.sharding.spec[0] == "data"
        assert w.addressable_shards[0].data.shape[0] * 8 == w.shape[0]
        # non-divisible leaves ([4] output bias on an 8-way axis) stay
        # replicated — correctness over forced sharding
        assert tr.params[1]["b"].sharding.is_fully_replicated

    def test_fsdp_composes_with_tensor_parallel(self, eight_devices):
        mesh = make_mesh(MeshSpec(data=4, model=2), devices=eight_devices)
        tr = ParallelTrainer(_net(), mesh, tensor_parallel=True,
                             shard_params="fsdp").init()
        x, y = _data()
        l0 = float(tr.step(x, y))
        assert np.isfinite(l0)
        spec = tr.params[0]["W"].sharding.spec
        assert spec[0] == "data" and spec[-1] == "model"

    def test_sync_to_net_gathers_full_copy(self, eight_devices):
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        tr = _trainer("fsdp", mesh)
        x, y = _data()
        tr.step(x, y)
        net = tr.sync_to_net()
        assert np.asarray(net.params[0]["W"]).shape == (8, 16)
        out = net.output(x)
        assert out.shape == (16, 4)
        # counters ride along so save_bundle(net) is a complete resume unit
        assert net.iteration == 1


class TestStreamedFSDP:
    """Tentpole (ISSUE 14): shard_params='fsdp_stream' — the homogeneous
    trunk scanned block-by-block, each block all-gathered INSIDE the scan
    body and discarded; step-peak = one block, not the model."""

    def test_trunk_detection(self, eight_devices):
        from deeplearning4j_tpu.parallel.data_parallel import \
            streamable_trunk
        net = _stream_net()
        params, state = net.init()
        assert streamable_trunk(net, params, state) == (1, 5)
        # heterogeneous net: no >=2 run of identical layers
        net2 = _net()
        p2, s2 = net2.init()
        assert streamable_trunk(net2, p2, s2) is None
        # a frozen trunk layer splits the run
        net3 = _stream_net()
        p3, s3 = net3.init()
        net3.frozen_layers = (3,)
        trunk = streamable_trunk(net3, p3, s3)
        assert trunk is not None and trunk[1] - trunk[0] == 2

    def test_unstreamable_net_raises(self, eight_devices):
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        with pytest.raises(ValueError, match="homogeneous trunk"):
            ParallelTrainer(_net(), mesh,
                            shard_params="fsdp_stream").init()

    def test_streamed_bit_exact_vs_replicated(self, eight_devices):
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        x, y = _data()
        ts = {m: _stream_trainer(m, mesh)
              for m in ("replicated", "fsdp", "fsdp_stream")}
        for _ in range(5):
            losses = {m: float(t.step(x, y)) for m, t in ts.items()}
        _assert_same_math(losses["fsdp_stream"], losses["replicated"])
        w_ref = np.asarray(ts["replicated"].params[1]["W"])
        _assert_same_math(ts["fsdp_stream"].params[1]["W"], w_ref)
        # stored layout: trunk weights sharded P('data') between steps
        w = ts["fsdp_stream"].params[1]["W"]
        assert w.sharding.spec[0] == "data"
        assert w.addressable_shards[0].data.shape[0] * 8 == w.shape[0]

    def test_streamed_dropout_and_l2_bit_exact(self, eight_devices):
        """The hard mirrors: the scan body must consume rng splits in
        exactly apply_fn's per-layer order (dropout + per-layer split)
        and re-add per-block penalties in original layer order — both
        bit-exact, or the streamed tier silently trains a different
        model."""
        def net():
            conf = NeuralNetConfig(seed=6,
                                   updater=U.Adam(learning_rate=0.01)) \
                .list(L.DenseLayer(n_out=64, activation="tanh"),
                      *[L.DenseLayer(n_out=64, activation="tanh", l2=0.01,
                                     dropout=0.2) for _ in range(3)],
                      L.OutputLayer(n_out=4, loss="mcxent"),
                      input_type=I.FeedForwardType(8))
            return MultiLayerNetwork(conf)

        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        x, y = _data()
        tr_r = ParallelTrainer(net(), mesh,
                               shard_optimizer_state=False).init()
        tr_s = ParallelTrainer(net(), mesh,
                               shard_params="fsdp_stream").init()
        assert tr_s._trunk == (1, 4)
        for _ in range(4):
            lr = float(tr_r.step(x, y))
            ls = float(tr_s.step(x, y))
        _assert_same_math(ls, lr)
        _assert_same_math(tr_s.params[1]["W"], tr_r.params[1]["W"])

    def test_streamed_fused_k4_bit_exact(self, eight_devices):
        """The K-step scan carries the streamed layout: a K=4 dispatch is
        a scan-of-scans whose carry stays in the P('data') storage for
        all K steps, bit-exact vs the K=1 replicated loop."""
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        x, y = _data(n=64)
        ref = _stream_trainer("replicated", mesh)
        ref.fit(x, y, batch_size=16, epochs=2)
        w_ref = np.asarray(ref.params[1]["W"])
        tr = _stream_trainer("fsdp_stream", mesh)
        tr.fit(x, y, batch_size=16, epochs=2, steps_per_dispatch=4)
        _assert_same_math(tr.params[1]["W"], w_ref)
        m = tr.opt_state["m"][1]["W"]
        assert m.sharding.spec[0] == "data"
        assert tr.iteration == ref.iteration

    def test_streamed_hlo_gathers_per_block_inside_loop(self,
                                                        eight_devices):
        """Acceptance: the lowered HLO has the per-block all-gather
        INSIDE the scan's while body — the gather count is independent
        of trunk depth and no gather is slab-shaped — while plain fsdp
        hoists one gather PER trunk layer to step entry."""
        import re
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        x, y = _data()

        def hlo(tr):
            tr.step(x, y)
            return tr._step_fn.lower(
                tr.params, tr.state, tr.opt_state, jnp.asarray(x),
                jnp.asarray(y), 0, tr._rng, None).compile().as_text()

        def ag_shapes(txt):
            return [tuple(int(d) for d in m.split(",") if d)
                    for m in re.findall(
                        r"= \S+?\[([0-9,]*)\]\S* all-gather", txt)]

        txt_s = hlo(_stream_trainer("fsdp_stream", mesh))
        txt_f = hlo(_stream_trainer("fsdp", mesh))
        trunk_w = [s for s in ag_shapes(txt_f) if s[-2:] == (64, 64)]
        assert len(trunk_w) >= 4            # fsdp: one gather per block
        stream_w = [s for s in ag_shapes(txt_s) if s[-2:] == (64, 64)]
        # streamed: a fixed number of block-shaped gathers (forward
        # in-loop + remat backward), NOT one per trunk layer...
        assert 1 <= len(stream_w) < 4
        # ...and never a whole-slab [4, 64, 64] gather hoisted to entry
        assert (4, 64, 64) not in ag_shapes(txt_s)
        # the scan lowered to a while loop (the gather lives in its body:
        # XLA cannot hoist a shape that depends on the loop counter)
        assert "while" in txt_s

    def test_streamed_step_peak_below_fsdp(self, eight_devices):
        """Acceptance: compiled.memory_analysis() step-peak for
        fsdp_stream strictly below plain fsdp at the same batch, and the
        ledger lands in train_memory_summary / the gauges."""
        from deeplearning4j_tpu import telemetry
        from deeplearning4j_tpu.telemetry import devices as _devices
        telemetry.reset()
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        x, y = _data()
        stats = {}
        for m in ("replicated", "fsdp", "fsdp_stream"):
            tr = _stream_trainer(m, mesh)
            stats[m] = tr.step_memory_analysis(x, y)
        if stats["fsdp"] is None:
            pytest.skip("backend has no memory_analysis")
        assert stats["fsdp_stream"]["temp_bytes"] \
            < stats["fsdp"]["temp_bytes"]
        assert stats["fsdp_stream"]["peak_bytes"] \
            < stats["fsdp"]["peak_bytes"] \
            < stats["replicated"]["peak_bytes"]
        snap = _devices.train_memory_summary()["parallel_trainer"]
        assert snap["step_peak_bytes"]["layout"] == "fsdp_stream"
        assert snap["step_peak_bytes"]["peak_bytes"] \
            == stats["fsdp_stream"]["peak_bytes"]
        telemetry.reset()
        assert "parallel_trainer" not in _devices.train_memory_summary()

    def test_step_peak_gauges_emitted(self, eight_devices):
        from deeplearning4j_tpu import telemetry
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        x, y = _data()
        telemetry.reset()
        reg = telemetry.get_registry()
        was = reg.enabled
        reg.enabled = True
        try:
            tr = _stream_trainer("fsdp_stream", mesh)
            stats = tr.step_memory_analysis(x, y)
            if stats is None:
                pytest.skip("backend has no memory_analysis")
            g = reg.get("step_peak_bytes")
            assert g is not None
            vals = {ls["component"]: g.value(**ls)
                    for ls in g.labelsets()
                    if ls.get("site") == "parallel_trainer"
                    and ls.get("layout") == "fsdp_stream"}
            assert vals["peak"] == stats["peak_bytes"]
            assert vals["temp"] == stats["temp_bytes"]
        finally:
            reg.enabled = was
            telemetry.reset()

    def test_aot_compile_exports_step_peak(self):
        """Every executable through the blessed compile site exports its
        ledger (site aot:<kind base>) — serving/fused AOT compiles get
        step-peak observability for free."""
        import jax
        from deeplearning4j_tpu import telemetry
        from deeplearning4j_tpu.telemetry import devices as _devices
        from deeplearning4j_tpu.utils import compile_cache as _cc
        telemetry.reset()
        fn = jax.jit(lambda a: a * 2.0)
        ex, src = _cc.aot_compile(fn, jnp.ones((4, 4)),
                                  kind="probe:smoke")
        assert src == "compile"
        snap = _devices.train_memory_summary().get("aot:probe", {})
        got = snap.get("step_peak_bytes")
        if got is not None:               # backend-dependent
            assert got["layout"] == "probe:smoke"
            assert got["output_bytes"] >= 4 * 4 * 4
        telemetry.reset()

    def test_sync_to_net_gathers_full_copy_streamed(self, eight_devices):
        """The chunked fit-end gather (satellite): a streamed trainer's
        sync_to_net still lands a complete host copy, counters included,
        namedtuple/dict/list containers preserved."""
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        tr = _stream_trainer("fsdp_stream", mesh)
        x, y = _data()
        tr.step(x, y)
        net = tr.sync_to_net()
        assert np.asarray(net.params[1]["W"]).shape == (64, 64)
        assert isinstance(net.opt_state, dict)
        assert np.asarray(net.opt_state["m"][1]["W"]).shape == (64, 64)
        assert net.iteration == 1
        out = net.output(x)
        assert out.shape == (16, 4)


class TestZeroHLO:
    """Acceptance: the collectives are read out of the lowered HLO.
    lax.psum_scatter (the distributed masters' exchange) lowers to a
    LITERAL `reduce-scatter` op everywhere incl. CPU; the jit/GSPMD
    trainer path gets whatever the backend pipeline picks — TPU/GPU fuse
    a reduce-scatter, CPU's partitioner emits the decomposed
    all-reduce + dynamic-slice pair feeding the shard-shaped update, with
    the param all-gather closing the loop. Both shapes are asserted."""

    def test_trainer_step_hlo_has_sharded_update_collectives(
            self, eight_devices):
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        tr = _trainer("zero1", mesh)
        x, y = _data()
        tr.step(x, y)
        txt = tr._step_fn.lower(
            tr.params, tr.state, tr.opt_state, jnp.asarray(x),
            jnp.asarray(y), 0, tr._rng, None).compile().as_text()
        reduce_scattered = "reduce-scatter" in txt
        decomposed = ("all-reduce" in txt and "dynamic-slice" in txt)
        assert reduce_scattered or decomposed, \
            "no grad-path reduce-scatter (fused or decomposed) in the HLO"
        # the sharded update's params must gather back out
        assert "all-gather" in txt

    def test_fsdp_step_hlo_gathers_params(self, eight_devices):
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        tr = _trainer("fsdp", mesh)
        x, y = _data()
        tr.step(x, y)
        txt = tr._step_fn.lower(
            tr.params, tr.state, tr.opt_state, jnp.asarray(x),
            jnp.asarray(y), 0, tr._rng, None).compile().as_text()
        assert "all-gather" in txt
        assert ("reduce-scatter" in txt
                or ("all-reduce" in txt and "dynamic-slice" in txt))

    def test_shared_master_step_hlo_has_literal_reduce_scatter(
            self, eight_devices):
        from deeplearning4j_tpu.parallel.distributed import \
            SharedTrainingMaster
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        net = _net(seed=3)
        net.init()
        x, y = _data(n=64, seed=2)
        master = SharedTrainingMaster(mesh, batch_size_per_worker=8)
        master.execute_training(net, x, y, epochs=1)
        w = master.n_workers
        opt_shards = jax.tree_util.tree_map(
            lambda a: np.zeros((w, (np.asarray(a).size + w - 1) // w),
                               np.float32), net.opt_state)
        resid = jax.tree_util.tree_map(
            lambda a: np.zeros((w,) + np.asarray(a).shape, np.float32),
            net.params)
        txt = master._step_fn.lower(
            net.params, net.state, opt_shards, resid, np.float32(0.0),
            x, y, 0, jax.random.PRNGKey(0)).compile().as_text()
        assert txt.count("reduce-scatter") > 0
        assert "all-gather" in txt


class TestDistributedZero:
    """Tentpole (d): the TrainingMasters' exchange shards updater state
    across workers instead of replicating (Shared) / pmean-ing full opt
    trees (ParameterAveraging)."""

    def test_shared_master_sharded_matches_replicated(self, eight_devices):
        from deeplearning4j_tpu.parallel.distributed import \
            SharedTrainingMaster
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        x, y = _data(n=64, seed=4)
        nets = {}
        for zero in (False, True):
            net = _net(seed=11)
            net.init()
            SharedTrainingMaster(
                mesh, batch_size_per_worker=8,
                shard_updater_state=zero).execute_training(
                    net, x, y, epochs=3)
            nets[zero] = net
        for lz, lr in zip(nets[True].params, nets[False].params):
            for k in lz:
                np.testing.assert_allclose(np.asarray(lz[k]),
                                           np.asarray(lr[k]),
                                           rtol=1e-6, atol=1e-7)
        # opt state reassembles to the param-shaped layout for
        # checkpoints AND for resuming another round
        for oz, orr in zip(nets[True].opt_state["m"],
                           nets[False].opt_state["m"]):
            for k in oz:
                assert np.asarray(oz[k]).shape == np.asarray(orr[k]).shape
                np.testing.assert_allclose(np.asarray(oz[k]),
                                           np.asarray(orr[k]),
                                           rtol=1e-6, atol=1e-8)

    def test_shared_master_resumes_from_reassembled_opt(self,
                                                        eight_devices):
        """The sharded run's end-state feeds a SECOND execute_training:
        the replicated↔sharded opt conversion round-trips."""
        from deeplearning4j_tpu.parallel.distributed import \
            SharedTrainingMaster
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        x, y = _data(n=64, seed=5)
        net = _net(seed=12)
        net.init()
        m = SharedTrainingMaster(mesh, batch_size_per_worker=8)
        m.execute_training(net, x, y, epochs=1)
        it_after = net.iteration
        loss = m.execute_training(net, x, y, epochs=1)
        assert np.isfinite(loss)
        assert net.iteration > it_after
        assert m.training_stats()["updater_state_sharded"]

    def test_scatter_pmean_equals_pmean(self, eight_devices):
        """The PA master's opt averaging decomposition is exactly a
        pmean (psum_scatter + all_gather IS the all-reduce, leaf shapes
        restored incl. a non-divisible tail)."""
        from deeplearning4j_tpu.parallel import distributed as D
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        tree = {"a": jnp.arange(24.0).reshape(8, 3),   # 24 % 8 == 0
                "b": jnp.arange(5.0)}                  # 5 % 8 != 0 (pads)

        def f(t):
            return (D._scatter_pmean(t, 8),
                    jax.tree_util.tree_map(
                        lambda a: jax.lax.pmean(a, "data"), t))

        got, want = jax.jit(shard_map(
            f, mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
            check_vma=False))(tree)
        for k in tree:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]))


class TestShardedBytesTelemetry:
    """Satellite: param_bytes / opt_state_bytes addressable-shard-aware
    gauges — the 1/N saving is a number, not a claim."""

    def test_per_device_bytes_read_one_nth(self, eight_devices):
        from deeplearning4j_tpu.telemetry import devices as _devices
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        tr = ParallelTrainer(_net(n_in=8, hidden=16, n_out=8), mesh).init()
        o_log, o_dev = _devices.tree_shard_bytes(tr.opt_state)
        assert o_dev * 8 == o_log  # every leaf divisible -> exactly 1/8
        p_log, p_dev = _devices.tree_shard_bytes(tr.params)
        assert p_dev == p_log      # ZeRO-1: params still replicated
        snap = _devices.train_memory_summary()["parallel_trainer"]
        assert snap["opt_state_bytes"]["per_device"] == o_dev
        assert snap["param_bytes"]["logical"] == p_log

    def test_fsdp_params_counted_sharded(self, eight_devices):
        from deeplearning4j_tpu.telemetry import devices as _devices
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        tr = ParallelTrainer(_net(n_in=8, hidden=16, n_out=8), mesh,
                             shard_params="fsdp").init()
        p_log, p_dev = _devices.tree_shard_bytes(tr.params)
        assert p_dev * 8 == p_log

    def test_health_payload_carries_train_memory(self, eight_devices):
        from deeplearning4j_tpu import telemetry
        from deeplearning4j_tpu.ui.server import _health_payload
        telemetry.reset()
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        ParallelTrainer(_net(), mesh).init()
        doc = _health_payload()
        tm = doc["train_memory"]["parallel_trainer"]
        assert tm["opt_state_bytes"]["per_device"] \
            < tm["opt_state_bytes"]["logical"]
        telemetry.reset()
        assert _health_payload()["train_memory"] == {}

    def test_gauges_emitted_when_registry_enabled(self, eight_devices):
        from deeplearning4j_tpu import telemetry
        from deeplearning4j_tpu.telemetry import devices as _devices
        telemetry.reset()
        reg = telemetry.get_registry()
        was = reg.enabled
        reg.enabled = True
        try:
            mesh = make_mesh(MeshSpec(data=8, model=1),
                             devices=eight_devices)
            ParallelTrainer(_net(n_in=8, hidden=16, n_out=8), mesh).init()
            g = reg.get("opt_state_bytes")
            assert g is not None
            vals = {ls["scope"]: g.value(**ls) for ls in g.labelsets()
                    if ls.get("site") == "parallel_trainer"}
            assert vals["per_device"] * 8 == vals["logical"]
        finally:
            reg.enabled = was
            telemetry.reset()


@pytest.mark.slow
class TestCheckpointLayoutRoundTrips:
    """Tentpole (e): every layout round-trips through sharded_checkpoint,
    INCLUDING resuming a replicated checkpoint into a sharded trainer and
    back — the layout is the trainer's policy, never baked into the
    file."""

    def _fit_some(self, tr, x, y, n=3):
        for _ in range(n):
            loss = tr.step(x, y)
        return float(np.asarray(loss))

    @pytest.mark.parametrize("src,dst", [("replicated", "zero1"),
                                         ("zero1", "replicated"),
                                         ("replicated", "fsdp"),
                                         ("fsdp", "zero1")])
    def test_cross_layout_resume_bit_exact(self, tmp_path, eight_devices,
                                           src, dst):
        from deeplearning4j_tpu.utils.sharded_checkpoint import (
            restore_trainer, save_trainer)
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        x, y = _data()
        tr = _trainer(src, mesh, seed=21)
        self._fit_some(tr, x, y)
        path = str(tmp_path / f"{src}_to_{dst}")
        save_trainer(path, tr)
        loss_next = float(np.asarray(tr.step(x, y)))  # uninterrupted

        tr2 = _trainer(dst, mesh, seed=21)
        restore_trainer(path, tr2)
        assert tr2.iteration == 3
        # restored arrays live in the DESTINATION layout
        m = tr2.opt_state["m"][0]["W"]
        if dst == "replicated":
            assert m.sharding.is_fully_replicated
        else:
            assert m.sharding.spec[0] == "data"
        if dst == "fsdp":
            assert tr2.params[0]["W"].sharding.spec[0] == "data"
        loss_resumed = float(np.asarray(tr2.step(x, y)))
        assert loss_resumed == loss_next

    @pytest.mark.parametrize("src,dst", [("replicated", "fsdp_stream"),
                                         ("fsdp_stream", "replicated"),
                                         ("fsdp", "fsdp_stream"),
                                         ("fsdp_stream", "zero1")])
    def test_cross_layout_resume_streamed(self, tmp_path, eight_devices,
                                          src, dst):
        """Satellite: the matrix extended to the streamed tier — same
        per-leaf storage layout as fsdp, only the step differs, so
        restore_trainer's layout-free template covers it unchanged."""
        from deeplearning4j_tpu.utils.sharded_checkpoint import (
            restore_trainer, save_trainer)
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        x, y = _data()
        tr = _stream_trainer(src, mesh, seed=23)
        self._fit_some(tr, x, y)
        path = str(tmp_path / f"{src}_to_{dst}")
        save_trainer(path, tr)
        loss_next = float(np.asarray(tr.step(x, y)))

        tr2 = _stream_trainer(dst, mesh, seed=23)
        restore_trainer(path, tr2)
        assert tr2.iteration == 3
        if dst in ("fsdp", "fsdp_stream"):
            assert tr2.params[1]["W"].sharding.spec[0] == "data"
        loss_resumed = float(np.asarray(tr2.step(x, y)))
        assert loss_resumed == loss_next

    @pytest.mark.parametrize("src_world,dst_world,src,dst",
                             [(8, 4, "zero1", "fsdp"),
                              (4, 8, "fsdp", "zero1"),
                              (8, 2, "fsdp", "fsdp")])
    def test_cross_world_size_resume_bit_exact(self, tmp_path,
                                               eight_devices, src_world,
                                               dst_world, src, dst):
        """ISSUE 15 satellite: the elastic path's single-process proof —
        a checkpoint saved by a world-size-N sharded trainer (8 devices =
        "2 hosts x 4") restores into a world-size-M one (4 devices = "1
        host"), every leaf BIT-EXACT and landing directly in the new 1/M
        layout: the world size is the destination trainer's policy, never
        the file's. This is the restore the hostfleet supervisor leans on
        when a generation re-forms at N-1."""
        from deeplearning4j_tpu.utils.sharded_checkpoint import (
            restore_trainer, save_trainer)
        mesh_src = make_mesh(MeshSpec(data=src_world),
                             devices=eight_devices[:src_world])
        mesh_dst = make_mesh(MeshSpec(data=dst_world),
                             devices=eight_devices[:dst_world])
        x, y = _data()  # n=16: divisible by every world size crossed here
        tr = _trainer(src, mesh_src, seed=41)
        self._fit_some(tr, x, y)
        path = str(tmp_path / f"w{src_world}_{src}_to_w{dst_world}_{dst}")
        save_trainer(path, tr)
        host = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda a: np.asarray(jax.device_get(a)), t)
        src_params, src_opt = host(tr.params), host(tr.opt_state)

        tr2 = _trainer(dst, mesh_dst, seed=41)
        restore_trainer(path, tr2)
        assert tr2.iteration == 3
        for a, b in zip(jax.tree_util.tree_leaves(src_params),
                        jax.tree_util.tree_leaves(host(tr2.params))):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(jax.tree_util.tree_leaves(src_opt),
                        jax.tree_util.tree_leaves(host(tr2.opt_state))):
            np.testing.assert_array_equal(a, b)
        # restored arrays live in the DESTINATION world's layout
        m = tr2.opt_state["m"][0]["W"]
        assert m.sharding.spec[0] == "data"
        assert len(m.sharding.device_set) == dst_world
        if dst == "fsdp":
            assert len(tr2.params[0]["W"].sharding.device_set) == dst_world
        # and the resumed step dispatches on the new topology
        assert np.isfinite(float(np.asarray(tr2.step(x, y))))

    def test_bundle_reshards_across_world_sizes(self, tmp_path,
                                                eight_devices):
        """The hostfleet recovery artifact exactly: a layout-free
        save_bundle zip written after training at world 8 adopts into a
        world-4 FSDP trainer — params/opt re-placed in the smaller
        world's 1/4 layout, counters and RNG chain intact, bit-exact."""
        from deeplearning4j_tpu.utils.serialization import (load_bundle,
                                                            save_bundle)
        mesh8 = make_mesh(MeshSpec(data=8), devices=eight_devices)
        mesh4 = make_mesh(MeshSpec(data=4), devices=eight_devices[:4])
        x, y = _data()
        tr = _trainer("fsdp", mesh8, seed=42)
        self._fit_some(tr, x, y)
        path = str(tmp_path / "world_cross_bundle.zip")
        save_bundle(tr.sync_to_net(), path)
        src_leaves = [np.asarray(l) for l in
                      jax.tree_util.tree_leaves(tr.net.params)]

        bundle = load_bundle(path)
        tr2 = ParallelTrainer(bundle.net, mesh4,
                              shard_params="fsdp").adopt_net_state()
        assert tr2.iteration == 3
        assert len(tr2.params[0]["W"].sharding.device_set) == 4
        for a, b in zip(src_leaves,
                        jax.tree_util.tree_leaves(jax.tree_util.tree_map(
                            lambda l: np.asarray(jax.device_get(l)),
                            tr2.params))):
            np.testing.assert_array_equal(a, b)
        assert np.isfinite(float(np.asarray(tr2.step(x, y))))

    def test_epoch_rides_the_sharded_checkpoint(self, tmp_path,
                                                eight_devices):
        """Satellite fix en route: the epoch counter resumes (it rode
        only the single-process zip before — a restored multi-epoch fit
        restarted its epoch listeners from 0)."""
        from deeplearning4j_tpu.utils.sharded_checkpoint import (
            restore_trainer, save_trainer)
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        x, y = _data()
        tr = _trainer("zero1", mesh, seed=24)
        tr.fit(x, y, batch_size=8, epochs=3)
        assert tr.epoch == 3
        path = str(tmp_path / "epoch_ride")
        save_trainer(path, tr)
        tr2 = _trainer("fsdp", mesh, seed=24)
        restore_trainer(path, tr2)
        assert tr2.epoch == 3
        assert tr2.iteration == tr.iteration

    def test_bundle_round_trip_into_streamed_trainer(self, tmp_path,
                                                     eight_devices):
        """Single-process zip path for the streamed tier: sync_to_net ->
        save_bundle -> load_bundle -> adopt_net_state into an
        fsdp_stream trainer; the resumed step matches the uninterrupted
        one."""
        from deeplearning4j_tpu.utils.serialization import (load_bundle,
                                                            save_bundle)
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        x, y = _data()
        tr = _stream_trainer("fsdp", mesh, seed=25)
        self._fit_some(tr, x, y)
        path = str(tmp_path / "stream_bundle.zip")
        save_bundle(tr.sync_to_net(), path)
        loss_next = float(np.asarray(tr.step(x, y)))

        bundle = load_bundle(path)
        tr2 = ParallelTrainer(bundle.net, mesh,
                              shard_params="fsdp_stream").adopt_net_state()
        assert tr2.iteration == 3
        assert tr2.params[1]["W"].sharding.spec[0] == "data"
        loss_resumed = float(np.asarray(tr2.step(x, y)))
        assert loss_resumed == loss_next

    def test_bundle_round_trip_into_sharded_trainer(self, tmp_path,
                                                    eight_devices):
        """The single-process zip path: sharded trainer -> sync_to_net ->
        save_bundle -> load_bundle -> adopt_net_state into an FSDP
        trainer; the resumed step matches the uninterrupted one."""
        from deeplearning4j_tpu.utils.serialization import (load_bundle,
                                                            save_bundle)
        mesh = make_mesh(MeshSpec(data=8, model=1), devices=eight_devices)
        x, y = _data()
        tr = _trainer("zero1", mesh, seed=22)
        self._fit_some(tr, x, y)
        path = str(tmp_path / "zero_bundle.zip")
        save_bundle(tr.sync_to_net(), path)
        loss_next = float(np.asarray(tr.step(x, y)))

        bundle = load_bundle(path)
        tr2 = ParallelTrainer(bundle.net, mesh,
                              shard_params="fsdp").adopt_net_state()
        assert tr2.iteration == 3
        assert tr2.params[0]["W"].sharding.spec[0] == "data"
        loss_resumed = float(np.asarray(tr2.step(x, y)))
        assert loss_resumed == loss_next
