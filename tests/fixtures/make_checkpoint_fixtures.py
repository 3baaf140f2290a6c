"""Regenerate the golden checkpoint fixtures (reference analog:
deeplearning4j-core regressiontest/ fixtures, RegressionTest050.java—080 —
zips from OLD versions pinned so format changes can never silently orphan
existing checkpoints).

Run from the repo root ONLY when intentionally bumping FORMAT_VERSION:

    env JAX_PLATFORMS=cpu \
        python tests/fixtures/make_checkpoint_fixtures.py

then commit the regenerated zips + expectations. Round-to-round, the zips
are NOT regenerated: the committed files from the previous round ARE the
regression test.
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from deeplearning4j_tpu.nn import layers as L, updaters as U
from deeplearning4j_tpu.nn.conf import inputs as I
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.utils.serialization import FORMAT_VERSION, save_model

HERE = os.path.dirname(os.path.abspath(__file__))


def _train_and_save(name, conf, x, y):
    net = MultiLayerNetwork(conf)
    net.fit(x, y, epochs=3, batch_size=len(x))  # a few Adam steps
    save_model(net, os.path.join(HERE, f"{name}_v{FORMAT_VERSION}.zip"))
    preds = np.asarray(net.output(x))
    np.save(os.path.join(HERE, f"{name}_v{FORMAT_VERSION}_expected.npy"), preds)
    np.save(os.path.join(HERE, f"{name}_v{FORMAT_VERSION}_input.npy"), x)
    return net


def main():
    rs = np.random.RandomState(42)

    # MLP
    x = rs.randn(8, 5).astype(np.float32)
    y = np.eye(3)[rs.randint(0, 3, 8)].astype(np.float32)
    mlp_conf = NeuralNetConfig(seed=1, updater=U.Adam(learning_rate=0.01)).list(
        L.DenseLayer(n_out=7, activation="tanh"),
        L.OutputLayer(n_out=3, loss="mcxent"),
        input_type=I.FeedForwardType(5))
    _train_and_save("mlp_adam", mlp_conf, x, y)

    # CNN
    xc = rs.rand(4, 8, 8, 1).astype(np.float32)
    yc = np.eye(2)[rs.randint(0, 2, 4)].astype(np.float32)
    cnn_conf = NeuralNetConfig(seed=2, updater=U.Adam(learning_rate=0.01)).list(
        L.ConvolutionLayer(n_out=3, kernel=(3, 3), activation="relu"),
        L.SubsamplingLayer(kernel=(2, 2), stride=(2, 2), mode="max"),
        L.OutputLayer(n_out=2, loss="mcxent"),
        input_type=I.convolutional(8, 8, 1))
    _train_and_save("cnn_adam", cnn_conf, xc, yc)

    # LSTM (rnn output loss over time)
    xr = rs.rand(3, 6, 4).astype(np.float32)
    yr = np.eye(2)[rs.randint(0, 2, (3, 6))].astype(np.float32)
    lstm_conf = NeuralNetConfig(seed=3, updater=U.Adam(learning_rate=0.01)).list(
        L.LSTM(n_out=5, activation="tanh"),
        L.RnnOutputLayer(n_out=2, loss="mcxent"),
        input_type=I.recurrent(4, 6))
    _train_and_save("lstm_adam", lstm_conf, xr, yr)

    manifest = {"format_version": FORMAT_VERSION,
                "fixtures": ["mlp_adam", "cnn_adam", "lstm_adam"]}
    with open(os.path.join(HERE, "checkpoint_manifest.json"), "w") as f:
        json.dump(manifest, f, indent=2)
    print("fixtures written for format v%d" % FORMAT_VERSION)


if __name__ == "__main__":
    main()
