"""Smoke tests: every example and tutorial script must run end-to-end
(reference analog: dl4j-examples CI — the tutorials double as living
documentation, so a broken one is a doc bug AND a smoke failure)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TUTORIALS = [
    "examples/tutorials/t01_multilayernetwork_and_computationgraph.py",
    "examples/tutorials/t02_data_iterators.py",
    "examples/tutorials/t03_logistic_regression.py",
    "examples/tutorials/t04_feed_forward.py",
    "examples/tutorials/t05_autoencoder_anomaly_detection.py",
    "examples/tutorials/t06_autoencoder_sequence_clustering.py",
    "examples/tutorials/t07_center_loss_embeddings.py",
    "examples/tutorials/t08_rnn_sequence_classification.py",
    "examples/tutorials/t09_transformer_language_model.py",
    "examples/tutorials/t10_scaling_parallelism.py",
    "examples/tutorials/t11_production_lifecycle.py",
    "examples/tutorials/t12_migrating_from_dl4j.py",
    "examples/tutorials/t13_pipeline_any_network_and_cjk.py",
    "examples/tutorials/t14_data_loading_and_genuine_fixtures.py",
    "examples/tutorials/t15_training_dashboard.py",
]
EXAMPLES = [
    "examples/lenet_mnist.py",
    "examples/char_rnn_generation.py",
    "examples/resnet50_data_parallel.py",
    "examples/sklearn_pipeline.py",
]


def _run(rel_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, os.path.join(REPO, rel_path)],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, f"{rel_path} failed:\n{r.stderr[-3000:]}"
    return r.stdout


@pytest.mark.slow
@pytest.mark.parametrize("script", TUTORIALS, ids=[os.path.basename(t)[:3]
                                                   for t in TUTORIALS])
def test_tutorial_runs(script):
    _run(script)


@pytest.mark.slow
@pytest.mark.parametrize("script", EXAMPLES,
                         ids=[os.path.basename(e).split(".")[0]
                              for e in EXAMPLES])
def test_example_runs(script):
    _run(script)
