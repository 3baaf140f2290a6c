"""Generate the cross-round DL4J-ModelSerializer-format golden fixtures
(reference analog: regressiontest/ RegressionTest050..080.java — zips from
an OLD version pinned so format/mapping changes can never silently orphan
checkpoints). These zips are in the REFERENCE'S OWN on-disk format
(configuration.json + legacy Nd4j binary coefficients), so they also pin
the import mapping (gate permutation, conv OIHW->HWIO, 'f'-order
unflatten) against drift.

Run from the repo root ONLY when intentionally revising the fixture set:

    env JAX_PLATFORMS=cpu \
        python tests/fixtures/make_dl4j_fixtures.py

then commit the zips + expected outputs. Round-to-round the committed
files ARE the regression test (tests/test_dl4j_import.py
TestDl4jRegressionFixtures loads them and pins outputs).
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from deeplearning4j_tpu.modelimport import dl4j
from deeplearning4j_tpu.nn import layers as L, updaters as U
from deeplearning4j_tpu.nn.conf import inputs as I
from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
from deeplearning4j_tpu.nn.graph import (ComputationGraph, ElementWiseVertex,
                                         GraphBuilder)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

HERE = os.path.dirname(os.path.abspath(__file__))
VERSION = 1


def main():
    rs = np.random.RandomState(99)

    # MLN: conv + BN + dense stack
    conf = MultiLayerConfiguration(
        layers=(L.ConvolutionLayer(n_out=4, kernel=(3, 3), padding="same",
                                   activation="relu"),
                L.BatchNormalization(),
                L.SubsamplingLayer(kernel=(2, 2), stride=(2, 2)),
                L.DenseLayer(n_out=8, activation="relu"),
                L.OutputLayer(n_out=3, activation="softmax")),
        input_type=I.convolutional(8, 8, 1), updater=U.Adam(1e-3))
    mln = MultiLayerNetwork(conf)
    mln.init()
    x = rs.rand(4, 8, 8, 1).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 4)]
    mln.fit(x, y, epochs=2)
    dl4j.write_multilayer_network(
        mln, os.path.join(HERE, f"dl4j_cnn_mln_v{VERSION}.zip"))
    np.save(os.path.join(HERE, f"dl4j_cnn_mln_v{VERSION}_input.npy"), x)
    np.save(os.path.join(HERE, f"dl4j_cnn_mln_v{VERSION}_expected.npy"),
            np.asarray(mln.output(x)))

    # MLN: GravesLSTM (peepholes + gate permutation under test)
    conf = MultiLayerConfiguration(
        layers=(L.GravesLSTM(n_out=6, activation="tanh"),
                L.RnnOutputLayer(n_out=3, activation="softmax")),
        input_type=I.recurrent(4, 7), updater=U.Sgd(0.05))
    lstm = MultiLayerNetwork(conf)
    lstm.init()
    xr = rs.randn(3, 7, 4).astype(np.float32)
    yr = np.eye(3, dtype=np.float32)[rs.randint(0, 3, (3, 7))]
    lstm.fit(xr, yr, epochs=2)
    dl4j.write_multilayer_network(
        lstm, os.path.join(HERE, f"dl4j_graveslstm_v{VERSION}.zip"))
    np.save(os.path.join(HERE, f"dl4j_graveslstm_v{VERSION}_input.npy"), xr)
    np.save(os.path.join(HERE, f"dl4j_graveslstm_v{VERSION}_expected.npy"),
            np.asarray(lstm.output(xr)))

    # ComputationGraph: residual conv (topo-ordered param layout under test)
    g = (GraphBuilder(updater=U.Adam(1e-3), seed=4)
         .add_inputs("in").set_input_types(I.convolutional(8, 8, 3))
         .add_layer("c1", L.ConvolutionLayer(n_out=4, kernel=(3, 3),
                                             padding="same",
                                             activation="relu"), "in")
         .add_layer("bn1", L.BatchNormalization(), "c1")
         .add_layer("c2", L.ConvolutionLayer(n_out=4, kernel=(3, 3),
                                             padding="same"), "bn1")
         .add_vertex("add", ElementWiseVertex(op="add"), "c2", "bn1")
         .add_layer("relu", L.ActivationLayer(activation="relu"), "add")
         .add_layer("pool", L.GlobalPoolingLayer(mode="avg"), "relu")
         .add_layer("out", L.OutputLayer(n_out=2, activation="softmax"),
                    "pool")
         .set_outputs("out"))
    cg = ComputationGraph(g.build())
    cg.init()
    xg = rs.rand(3, 8, 8, 3).astype(np.float32)
    yg = np.eye(2, dtype=np.float32)[rs.randint(0, 2, 3)]
    cg.fit(xg, yg)
    dl4j.write_computation_graph(
        cg, os.path.join(HERE, f"dl4j_residual_cg_v{VERSION}.zip"))
    np.save(os.path.join(HERE, f"dl4j_residual_cg_v{VERSION}_input.npy"), xg)
    np.save(os.path.join(HERE, f"dl4j_residual_cg_v{VERSION}_expected.npy"),
            np.asarray(cg.output(xg)))

    manifest = {"version": VERSION,
                "fixtures": [
                    {"name": f"dl4j_cnn_mln_v{VERSION}", "kind": "mln",
                     "input_type": ["conv", 8, 8, 1]},
                    {"name": f"dl4j_graveslstm_v{VERSION}", "kind": "mln",
                     "input_type": ["rnn", 4, 7]},
                    {"name": f"dl4j_residual_cg_v{VERSION}", "kind": "graph",
                     "input_type": ["conv", 8, 8, 3]},
                ]}
    with open(os.path.join(HERE, "dl4j_manifest.json"), "w") as f:
        json.dump(manifest, f, indent=2)
    print(f"dl4j-format fixtures written, v{VERSION}")


if __name__ == "__main__":
    main()
