"""Continuous-learning tier (deeplearning4j_tpu/continuous), in-process
half: StepDriver round semantics + checkpoint/restore bit-exactness, the
ContinuousTrainer recovery policy (rollback on NumericsError with parity
vs. a run that never saw the poison, counted staleness drops, sick
snapshots never published, serving hot-swap handoff), and the ISSUE 13
satellites (AsyncDataSetIterator transient retry, bounded pubsub queues
with counted drops). The REAL-subprocess chaos legs live in
test_continuous_process.py."""

import queue as _queue
import time

import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.continuous import chaos
from deeplearning4j_tpu.continuous.driver import StepDriver
from deeplearning4j_tpu.continuous.trainer import (ContinuousTrainer,
                                                   StreamingTrainSource,
                                                   registry_updater)
from deeplearning4j_tpu.datasets.iterator import (AsyncDataSetIterator,
                                                  DataSet, DataSetIterator)
from deeplearning4j_tpu.telemetry import health
from deeplearning4j_tpu.utils.serialization import load_bundle


@pytest.fixture(autouse=True)
def _isolate():
    telemetry.reset()
    telemetry.disable()
    yield
    telemetry.disable()
    telemetry.reset()


def _net(seed=0):
    return chaos.smoke_net(seed=seed)


def _factory(batches):
    """zero-arg batch factory over a fixed (x, y) list — the fit-loop
    contract StepDriver consumes."""
    return lambda: iter([(x, y, None) for x, y in batches])


# ---------------------------------------------------------------------------
# StepDriver: rounds, checkpoint, restore
# ---------------------------------------------------------------------------


class TestStepDriver:
    def test_run_round_consumes_exactly_k_dispatches(self):
        batches = chaos.gen_batches(1, 5)
        net = _net()
        net.init()
        drv = StepDriver(net, _factory(batches))
        rr = drv.run_round(2)
        assert rr.dispatches == 2 and rr.steps == 2 and not rr.epoch_done
        assert net.iteration == 2
        rr = drv.run_round(None)
        assert rr.dispatches == 3 and rr.epoch_done
        assert net.iteration == 5 and net.epoch == 1

    def test_round_boundary_checkpoint_resume_bit_exact(self, tmp_path):
        """Stop after round R, bundle, resume in a FRESH process-alike
        (new net, new driver) over the remaining stream: bit-exact with
        the uninterrupted run, RNG chain included."""
        batches = chaos.gen_batches(7, 6)
        ref = _net()
        ref.init()
        StepDriver(ref, _factory(batches)).run_round(None)
        want = chaos.state_digest(ref)

        net = _net()
        net.init()
        drv = StepDriver(net, _factory(batches))
        drv.run_round(3)
        path = str(tmp_path / "mid.zip")
        drv.checkpoint(path)

        resumed = load_bundle(path).net
        drv2 = StepDriver(resumed, _factory(batches[3:]))
        drv2.run_round(None)
        assert chaos.state_digest(resumed) == want

    def test_restore_rolls_back_bit_exact_zero_recompiles(self, tmp_path):
        telemetry.enable()
        batches = chaos.gen_batches(3, 6)
        net = _net()
        net.init()
        drv = StepDriver(net, _factory(batches))
        drv.run_round(2)
        path = str(tmp_path / "good.zip")
        drv.checkpoint(path)
        want = chaos.state_digest(net)
        reg = telemetry.get_registry()

        drv.run_round(2)  # "bad" work to be rolled back
        assert chaos.state_digest(net) != want
        c = reg.get("recompiles_total")
        before = 0 if c is None else c.value(site="fit.step")
        drv.restore(path)
        assert chaos.state_digest(net) == want
        # the re-armed trees share shapes/dtypes: the cached step
        # re-dispatches without a recompile
        drv.run_round(1)
        c = reg.get("recompiles_total")
        after = 0 if c is None else c.value(site="fit.step")
        assert after == before

    def test_fused_engine_rounds(self):
        batches = chaos.gen_batches(9, 6)
        net = _net()
        net.init()
        drv = StepDriver(net, _factory(batches), k=2, batch_size=8,
                         prefetch=False)
        try:
            rr = drv.run_round(1)
            assert rr.dispatches == 1 and rr.steps == 2
            assert net.iteration == 2
            rr = drv.run_round(None)
            assert rr.epoch_done and net.iteration == 6
        finally:
            drv.close_source()

    def test_fit_facades_delegate_to_driver(self, monkeypatch):
        """The acceptance claim made mechanical: MLN.fit, CG.fit and
        ParallelTrainer.fit all route through StepDriver."""
        seen = []
        orig_run = StepDriver.run
        orig_round = StepDriver.run_round

        def spy_run(self, epochs):
            seen.append(type(self.net).__name__)
            return orig_run(self, epochs)

        def spy_round(self, k=None):
            seen.append(type(self.net).__name__)
            return orig_round(self, k)

        monkeypatch.setattr(StepDriver, "run", spy_run)
        monkeypatch.setattr(StepDriver, "run_round", spy_round)
        x = np.random.RandomState(0).rand(8, 12).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[
            np.random.RandomState(1).randint(0, 3, 8)]
        net = _net()
        net.fit(x, y, batch_size=4)
        assert "MultiLayerNetwork" in seen

        from deeplearning4j_tpu.nn import layers as L
        from deeplearning4j_tpu.nn import updaters as U
        from deeplearning4j_tpu.nn.conf import inputs as I
        from deeplearning4j_tpu.nn.graph import ComputationGraph, GraphBuilder
        g = ComputationGraph(
            (GraphBuilder(seed=3, updater=U.Adam(learning_rate=0.03))
             .add_inputs("in").set_input_types(I.FeedForwardType(12))
             .add_layer("d", L.DenseLayer(n_out=8), "in")
             .add_layer("out", L.OutputLayer(n_out=3, loss="mcxent"), "d")
             .set_outputs("out").build()))
        g.init()
        g.fit(x, y, batch_size=4)
        assert "ComputationGraph" in seen

        from deeplearning4j_tpu.parallel.data_parallel import ParallelTrainer
        t = ParallelTrainer(_net())
        t.fit(x, y)  # one batch of 8: divisible by any CPU-mesh data axis
        assert "ParallelTrainer" in seen


# ---------------------------------------------------------------------------
# ContinuousTrainer: recovery policy
# ---------------------------------------------------------------------------


class TestContinuousTrainer:
    def test_rollback_on_poison_bit_exact_parity(self, tmp_path):
        """A NaN batch trips the watchdog one round late; rollback to the
        last good bundle + resume is bit-exact with a run that never saw
        the poison — RNG chain included (the chaos gate's core claim)."""
        telemetry.enable()
        n, poison = 7, 3
        bad = chaos.gen_batches(11, n, poison={poison})
        good = [b for i, b in enumerate(chaos.gen_batches(11, n))
                if i != poison]

        net = _net()
        tr = ContinuousTrainer(net, list(bad),
                               snapshot_path=str(tmp_path / "snap.zip"))
        summary = tr.run()
        assert summary["rollbacks"] == 1
        assert net.iteration == n - 1

        ref = _net()
        ref.fit(iter(good), epochs=1)
        assert chaos.state_digest(net) == chaos.state_digest(ref)

        reg = telemetry.get_registry()
        assert reg.get("continuous_rollback_total") \
                  .value(reason="numerics") == 1
        assert reg.get("continuous_rolled_back_steps_total").value() == 1

    def test_rollback_budget_exhausted_reraises(self, tmp_path):
        telemetry.enable()
        bad = chaos.gen_batches(5, 6, poison={1, 2, 3, 4})
        tr = ContinuousTrainer(_net(), list(bad),
                               snapshot_path=str(tmp_path / "s.zip"),
                               max_rollbacks=2)
        with pytest.raises(health.NumericsError):
            tr.run()
        assert tr.rollbacks == 3  # 2 allowed + the one that re-raised

    def test_sick_snapshot_never_published(self, tmp_path):
        """policy=record keeps training through the poison (no rollback)
        — but the snapshot gate must refuse to hand the sick state to
        serving, counted."""
        telemetry.enable()
        n, poison = 5, 1
        bad = chaos.gen_batches(13, n, poison={poison})
        served = []
        tr = ContinuousTrainer(_net(), list(bad),
                               snapshot_path=str(tmp_path / "s.zip"),
                               health_policy="record",
                               serve_update=served.append)
        tr.run()
        reg = telemetry.get_registry()
        skipped = reg.get("continuous_snapshots_total") \
                     .value(verdict="skipped_sick")
        assert skipped >= 1
        # every snapshot that DID publish (and reach serving) is finite
        for path in served:
            b = load_bundle(path)
            for leaf in b.net.params[0].values():
                assert np.isfinite(np.asarray(leaf)).all()

    def test_serve_update_registry_hot_swap(self, tmp_path):
        telemetry.enable()
        from deeplearning4j_tpu.serving.registry import ModelRegistry
        net = _net()
        net.init()  # the registry warms its engine from concrete params
        registry = ModelRegistry()
        registry.register("cont", net, buckets=[8], input_spec=(12,))
        try:
            tr = ContinuousTrainer(
                net, list(chaos.gen_batches(17, 4)),
                snapshot_path=str(tmp_path / "s.zip"),
                serve_update=registry_updater(registry, "cont"))
            tr.run()
            reg = telemetry.get_registry()
            assert reg.get("continuous_serve_updates_total") \
                      .value(outcome="ok") >= 1
            probe = chaos.gen_batches(99, 1)[0][0]
            served = np.asarray(registry.output("cont", probe))
            direct = np.asarray(net.output(probe))
            assert float(np.max(np.abs(served - direct))) <= 1e-6
        finally:
            registry.unregister("cont")

    def test_quiet_stream_ends_counted_never_hangs(self, tmp_path):
        telemetry.enable()

        class Quiet(DataSetIterator):
            batch_size = None

            def reset(self):
                pass

            def __next__(self):
                raise TimeoutError("stream quiet")

        tr = ContinuousTrainer(_net(), Quiet(),
                               snapshot_path=str(tmp_path / "s.zip"),
                               ingest_retries=1, ingest_backoff_s=0.01)
        t0 = time.monotonic()
        summary = tr.run()
        assert summary["status"] == "stream_quiet"
        assert time.monotonic() - t0 < 30
        reg = telemetry.get_registry()
        assert reg.get("etl_retry_total").value(outcome="fatal") == 1


# ---------------------------------------------------------------------------
# bounded-staleness admission (StreamingTrainSource over real pubsub)
# ---------------------------------------------------------------------------


class TestStalenessAdmission:
    def test_stale_batch_dropped_fresh_admitted(self):
        telemetry.enable()
        from deeplearning4j_tpu.streaming.pubsub import (NDArrayPublisher,
                                                         NDArraySubscriber,
                                                         StreamingBroker)
        broker = StreamingBroker().start()
        try:
            sub = NDArraySubscriber("t", port=broker.port)
            pub = NDArrayPublisher("t", port=broker.port)
            src = StreamingTrainSource(sub, max_staleness_s=0.3,
                                       quiet_timeout_s=2.0)
            x, y = chaos.gen_batches(1, 1)[0]
            pub.publish_dataset(x, y, ts=time.time() - 5.0)  # born stale
            pub.publish_dataset(x, y)                        # fresh
            ds = next(src)
            assert isinstance(ds, DataSet)
            assert src.stale_dropped == 1 and src.admitted == 1
            reg = telemetry.get_registry()
            assert reg.get("continuous_dropped_total") \
                      .value(reason="stale") == 1
            pub.close()
            sub.close()
        finally:
            broker.close()

    def test_nonfinite_screen_optional(self):
        class FakeSub:
            def __init__(self, items):
                self.items = list(items)
                self.queue = _queue.Queue()
                import threading
                self._closed = threading.Event()

            def receive_timed(self, timeout=None):
                if not self.items:
                    self._closed.set()
                    raise StopIteration
                return 0.0, self.items.pop(0), None

        x, y = chaos.gen_batches(2, 1)[0]
        bad = x.copy()
        bad[0, 0] = np.inf
        src = StreamingTrainSource(FakeSub([(bad, y), (x, y)]),
                                   screen_nonfinite=True)
        ds = next(src)
        assert np.isfinite(ds.features).all()
        assert src.nonfinite_dropped == 1


# ---------------------------------------------------------------------------
# satellite: AsyncDataSetIterator transient retry
# ---------------------------------------------------------------------------


class _Flaky(DataSetIterator):
    """Yields n batches; raises ``exc`` ``fail_times`` times before each
    yield of batch index ``fail_at``."""

    def __init__(self, n=3, fail_at=1, fail_times=2, exc=ConnectionError):
        self.n = n
        self.fail_at = fail_at
        self.fail_times = fail_times
        self.exc = exc
        self._i = 0
        self._fails = 0

    batch_size = 4

    def reset(self):
        self._i = 0
        self._fails = 0

    def __next__(self):
        if self._i >= self.n:
            raise StopIteration
        if self._i == self.fail_at and self._fails < self.fail_times:
            self._fails += 1
            raise self.exc("transient")
        self._i += 1
        x = np.zeros((4, 2), np.float32)
        return DataSet(features=x, labels=x)


class TestAsyncRetry:
    def test_transient_errors_retried_then_recovered(self):
        telemetry.enable()
        it = AsyncDataSetIterator(_Flaky(fail_times=2), device_put=False,
                                  retry_transient=3, retry_backoff_s=0.001)
        got = sum(1 for _ in it)
        assert got == 3  # nothing lost
        reg = telemetry.get_registry()
        assert reg.get("etl_retry_total").value(outcome="retried") == 2
        assert reg.get("etl_retry_total").value(outcome="recovered") == 1
        assert reg.get("etl_retry_total").value(outcome="fatal") == 0

    def test_budget_exhausted_fatal_and_prompt(self):
        telemetry.enable()
        it = AsyncDataSetIterator(_Flaky(fail_times=99), device_put=False,
                                  retry_transient=2, retry_backoff_s=0.001)
        t0 = time.monotonic()
        with pytest.raises(ConnectionError):
            list(it)
        assert time.monotonic() - t0 < 10  # prompt, not a hang
        reg = telemetry.get_registry()
        assert reg.get("etl_retry_total").value(outcome="fatal") == 1
        assert reg.get("etl_retry_total").value(outcome="retried") == 2
        it.close()

    def test_default_is_fail_on_first(self):
        """Retry is OPT-IN: the default keeps the historical contract (a
        generator source closes on its first raise, so a default-on
        retry would silently truncate epochs)."""
        telemetry.enable()
        it = AsyncDataSetIterator(_Flaky(fail_times=1), device_put=False)
        with pytest.raises(ConnectionError):
            list(it)
        reg = telemetry.get_registry()
        assert reg.get("etl_retry_total").value(outcome="retried") == 0
        it.close()

    def test_non_retryable_errors_untouched(self):
        telemetry.enable()
        it = AsyncDataSetIterator(_Flaky(fail_times=1, exc=ValueError),
                                  device_put=False, retry_transient=3)
        with pytest.raises(ValueError):
            list(it)
        reg = telemetry.get_registry()
        assert reg.get("etl_retry_total").value(outcome="retried") == 0
        it.close()


# ---------------------------------------------------------------------------
# satellite: bounded pubsub queues, counted drops
# ---------------------------------------------------------------------------


class TestBoundedPubsub:
    def test_subscriber_drop_oldest_counted(self):
        telemetry.enable()
        from deeplearning4j_tpu.streaming.pubsub import (NDArrayPublisher,
                                                         NDArraySubscriber,
                                                         StreamingBroker)
        broker = StreamingBroker().start()
        try:
            sub = NDArraySubscriber("t", port=broker.port, buffer=2)
            pub = NDArrayPublisher("t", port=broker.port)
            # the broker registers a subscription some time after the
            # constructor returns, and what is published before that is
            # lost: keep publishing until enough has arrived to overflow
            deadline, i = time.time() + 10, 0
            while sub.dropped < 6 - 2 and time.time() < deadline:
                pub.publish(np.full((4,), i, np.float32))
                i += 1
                time.sleep(0.02)
            time.sleep(0.3)  # the last publish has landed or been dropped
            assert sub.dropped >= 6 - 2  # all but the buffered tail
            # the survivors are the NEWEST payloads, decodable
            age, arr, _ts = sub.receive_timed(timeout=2)
            assert arr[0] >= 2  # oldest were dropped
            reg = telemetry.get_registry()
            assert reg.get("stream_dropped_total") \
                      .value(site="subscriber") == sub.dropped
            pub.close()
            sub.close()
        finally:
            broker.close()

    def test_broker_outbox_drop_oldest_counted(self):
        """A subscriber that never reads must not stall the topic: the
        broker's bounded outbox drops oldest, counted, while other
        subscribers keep receiving."""
        telemetry.enable()
        import socket as _socket
        from deeplearning4j_tpu.streaming.pubsub import (NDArrayPublisher,
                                                         NDArraySubscriber,
                                                         StreamingBroker)
        import queue as _queue

        broker = StreamingBroker(subscriber_buffer=2).start()
        try:
            def await_subs(n):
                deadline = time.time() + 10
                while time.time() < deadline:
                    with broker._lock:
                        if len(broker._subs["t"]) == n:
                            return
                    time.sleep(0.02)
                raise AssertionError(f"subscription {n} never registered")

            # healthy FIRST (so its outbox is deterministically
            # _subs['t'][0] — the two SUB handshakes otherwise race)
            healthy = NDArraySubscriber("t", port=broker.port)
            await_subs(1)
            # then a raw, never-reading subscriber with a tiny receive
            # buffer (set BEFORE connect, or the kernel ignores it)
            wedged = _socket.socket()
            wedged.setsockopt(_socket.SOL_SOCKET, _socket.SO_RCVBUF, 4096)
            wedged.connect(("127.0.0.1", broker.port))
            wedged.sendall(b"SUB t\n")
            await_subs(2)
            pub = NDArrayPublisher("t", port=broker.port)
            payload = np.random.RandomState(0).rand(512, 1024) \
                .astype(np.float32)  # 2 MiB: wedges its writer fast
            for _ in range(12):
                pub.publish(payload)
            # the publisher never stalled behind the wedged subscriber:
            # frames keep REACHING the healthy one. Under CPU contention
            # drop-oldest may legitimately trim a lagging healthy reader
            # too — what it may never do is starve it or lose a frame
            # UNCOUNTED, so drain what arrived and balance the books
            # against the healthy path's own drop counters.
            got = 0
            while got < 12:
                try:
                    age, arr, _ts = healthy.receive_timed(timeout=3.0)
                except _queue.Empty:
                    break
                assert arr.shape == (512, 1024)
                got += 1
            assert got >= 1, "healthy subscriber starved behind the wedge"
            with broker._lock:
                healthy_box = broker._subs["t"][0]
            assert got + healthy_box.dropped + healthy.dropped == 12, \
                (f"silent loss on the healthy path: received {got}, "
                 f"broker-dropped {healthy_box.dropped}, subscriber-"
                 f"dropped {healthy.dropped} of 12")
            deadline = time.time() + 10
            while broker.dropped_total() == 0 and time.time() < deadline:
                time.sleep(0.05)
            assert broker.dropped_total() >= 1
            reg = telemetry.get_registry()
            assert reg.get("stream_dropped_total") \
                      .value(site="broker") == broker.dropped_total()
            pub.close()
            healthy.close()
            wedged.close()
        finally:
            broker.close()

    def test_publish_timestamp_ages_receive(self):
        from deeplearning4j_tpu.streaming import codec
        x, y = chaos.gen_batches(3, 1)[0]
        buf = codec.encode_dataset(x, y, ts=time.time() - 2.0)
        assert codec.dataset_ts(buf) is not None
        f, l = codec.decode_dataset(buf)
        np.testing.assert_array_equal(f, x)
        # and a payload without ts still decodes (back-compat)
        f2, _l2 = codec.decode_dataset(codec.encode_dataset(x, y))
        np.testing.assert_array_equal(f2, x)
