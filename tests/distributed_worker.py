"""2-process jax.distributed worker used by test_distributed_multiprocess.py.

Usage: python distributed_worker.py <process_id> <num_processes> <coord_port>
           [init_timeout_s] [init_retries]

Each process owns ONE local CPU device; jax.distributed joins them into a
2-device global mesh and SharedTrainingMaster's psum rides the cross-process
collective transport — the multi-host execution path the reference exercises
via local-mode Spark clusters (BaseSparkTest.java:89).

Failure protocol (ISSUE 15 satellite): an init that cannot reach the
coordinator exits ``procutil.INIT_FAILED_RC`` with ONE JSON error line
(carrying the ``distributed_init_total`` outcome counters) instead of
hanging into the spawner's 300 s communicate timeout. (The installed
jax's CPU client executes cross-process computations over Gloo, so the
training leg itself runs here and is not skipped.)
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import procutil  # noqa: E402 — shared subprocess plumbing

procutil.pin_single_cpu_device()  # BEFORE jax: one local CPU device

import jax  # noqa: E402


def main():
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    timeout_s = int(sys.argv[4]) if len(sys.argv) > 4 else 60
    retries = int(sys.argv[5]) if len(sys.argv) > 5 else 0
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.parallel.distributed import (
        SharedTrainingMaster, initialize_distributed)

    telemetry.enable()

    def init_series():
        # the shared wire form ("outcome=ok": n) every worker/bench emit
        # site uses — one flattening definition (telemetry.series_map)
        return telemetry.series_map("distributed_init_total")

    try:
        assert initialize_distributed(
            coordinator_address=f"127.0.0.1:{port}", num_processes=nproc,
            process_id=pid, initialization_timeout=timeout_s,
            connect_retries=retries)
    except Exception as e:  # noqa: BLE001 — distinct rc + one JSON line
        print(json.dumps({"error": str(e)[:500], "stage": "init",
                          "process": pid,
                          "distributed_init_total": init_series()}),
              flush=True)
        sys.exit(procutil.INIT_FAILED_RC)
    assert len(jax.local_devices()) == 1
    assert len(jax.devices()) == nproc, jax.devices()

    import numpy as np
    from jax.sharding import Mesh
    from deeplearning4j_tpu.nn import layers as L, updaters as U
    from deeplearning4j_tpu.nn.conf import inputs as I
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    rs = np.random.RandomState(0)  # same data on every process
    x = rs.randn(32, 6).astype(np.float32)
    y = np.eye(3)[rs.randint(0, 3, 32)].astype(np.float32)

    conf = NeuralNetConfig(seed=11, updater=U.Sgd(learning_rate=0.1)).list(
        L.DenseLayer(n_out=8, activation="tanh"),
        L.OutputLayer(n_out=3, loss="mcxent"),
        input_type=I.FeedForwardType(6))
    net = MultiLayerNetwork(conf)
    net.init()

    mesh = Mesh(np.array(jax.devices()), ("data",))
    master = SharedTrainingMaster(mesh, batch_size_per_worker=8,
                                  threshold=None)  # exact psum mode
    loss = master.execute_training(net, x, y, epochs=3)

    leaves = jax.tree_util.tree_leaves(net.params)
    checksum = float(sum(np.abs(np.asarray(l)).sum() for l in leaves))
    print(json.dumps({"process": pid, "loss": loss, "checksum": checksum,
                      "n_devices": len(jax.devices())}), flush=True)


if __name__ == "__main__":
    main()
