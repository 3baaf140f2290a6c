"""chip_smoke.py at toy size on the CPU: the phase bodies are the same code
the chip runs at full width (kernels in interpret mode here), and the script
itself must refuse to report anything without a chip."""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

from deeplearning4j_tpu import serving, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)

TOY = dict(vocab=64, n_layers=1, d_model=32, n_heads=2, seq_len=16)


@pytest.fixture(autouse=True)
def _isolate():
    telemetry.reset()
    yield
    serving.registry.reset()
    telemetry.reset()
    telemetry.disable()


def _last_json(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_train_then_serve_phases_at_toy_size(tmp_path, capsys):
    net = smoke.build_net(**TOY)
    # what the chip run relies on: a failed check raises. On the CPU the
    # compiled step holds no tpu_custom_call, so demanding the two of a
    # layer (the flash forward and backward kernel) must fail.
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        smoke.train_phase(net, vocab=TOY["vocab"], seq_len=TOY["seq_len"],
                          batch=2, steps=1, k=2, workdir=str(tmp_path),
                          flash_calls=2 * TOY["n_layers"])
    doc = smoke.train_phase(net, vocab=TOY["vocab"], seq_len=TOY["seq_len"],
                            batch=2, steps=2, k=2, workdir=str(tmp_path))
    assert doc == _last_json(capsys)
    assert doc["phase"] == "train" and doc["platform"] == "cpu"
    assert len(doc["losses_k1"]) == 4 and len(doc["losses_k2"]) == 2
    assert doc["compile_cache_events"]["hit"] >= 1
    assert doc["tpu_custom_calls"] == {"k1": 0, "k2": 0}  # gate closed

    doc = smoke.serve_phase(net, vocab=TOY["vocab"], batch_buckets=(1, 2),
                            seq_buckets=(8, 16), lengths=(8, 16, 5, 11),
                            tol=1e-5)
    assert doc["phase"] == "serve"
    assert doc["aot"]["warmed"] == 4
    assert doc["aot"]["lazy_compiles"] == doc["aot"]["jit_serves"] == 0


def test_kernels_phase_in_interpret_mode(kernel_dispatch):
    with kernel_dispatch():  # the flash cases run on the dispatch's blocks
        doc = smoke.kernels_phase(interpret=True,
                                  tol={"fwd": 1e-4, "bwd": 1e-4})
    names = {r["kernel"] for r in doc["results"]}
    assert {"flash_causal", "flash_padding_mask", "flash_block",
            "gated_delta_kernels", "causal_conv_silu", "causal_conv_gates",
            "causal_conv_bias_silu", "ssd_chunkwise", "ssd_kernels",
            "lstm_resident",
            "lstm_resident_peephole_masked", "lstm_tiled_masked"} == names


@pytest.mark.parametrize("kw,live,rectangle", [
    (dict(bh=2, t=512, d=128, block_diffusion=(256, 4)), 8, 16),
    (dict(bh=2, t=300, d=64), 6, 9)], ids=["block_diffusion", "causal"])
def test_flash_steps_time_at_toy_size(kw, live, rectangle):
    """The step that reads a dead turn's cost again on the chip: here its
    plumbing (both kernels alone on folded operands, the counts beside the
    two times, which on the CPU are the interpreter's and mean nothing)."""
    r = smoke._flash_steps_time("toy", block=128, interpret=True, iters=1,
                                **kw)
    assert (r["live"], r["rectangle"]) == (live, rectangle)
    assert r["fwd_ms"] > 0 and r["bwd_ms"] > 0


def test_looped_block_case_at_toy_size():
    """Off the chip both sides take the naive branch: the case's own
    plumbing (the block's fields, shapes, the comparison) is what runs."""
    r = smoke._looped_block_case("looped_toy", b=2, t=16, width=32, h=2,
                                 d=16, ffn=48, interpret=True,
                                 tol={"fwd": 1e-6, "bwd": 1e-6})
    assert r["kernel"] == "looped_toy" and r["fwd_rel_err"] == 0.0


def test_flash_backward_time_at_toy_size(kernel_dispatch):
    """The interpreted kernel's pullback runs and is timed; the number is
    the host's and means nothing here."""
    with kernel_dispatch():
        r = smoke._flash_backward_time("bwd_toy", b=1, t=128, h=1, d=16,
                                       interpret=True, iters=1)
    assert r["kernel"] == "bwd_toy" and r["bwd_ms"] > 0


def test_flash_rounded_once_case_at_toy_size(kernel_dispatch):
    """The interpreter multiplies in float32, so rounding the inputs shows
    (the case expects nothing there); the record names all four arrays."""
    with kernel_dispatch():
        r = smoke._flash_rounded_once_case("rounded_toy", b=1, t=128, h=2,
                                           d=16, interpret=True)
    assert set(r["bit_equal"]) == set(r["sha256"]) == {"out", "dq", "dk",
                                                       "dv"}
    assert not any(r["bit_equal"].values())
    assert 0 < r["max_abs_diff"]["out"] < 0.05


def test_multichip_phase_on_the_virtual_mesh(tmp_path, eight_devices):
    doc = smoke.multichip_phase(
        lambda: smoke.build_net(**TOY), vocab=TOY["vocab"],
        seq_len=TOY["seq_len"], n_chips=4, global_batch=8, parity_batch=4,
        steps=2, loss_tol=1e-5, batch_buckets=(1, 2), seq_buckets=(8, 16),
        lengths=(16, 5), serve_tol=1e-5, workdir=str(tmp_path))
    assert doc["buffers_on_devices"] == [0, 1, 2, 3]
    assert doc["opt_state_leaves_sharded"] > 0
    assert doc["warm_manifest_round_trips"] == {
        "mesh": {"warmed": 2, "manifest_hits": 2},
        "one_device": {"warmed": 4, "manifest_hits": 4}}


def test_script_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode != 0
    assert r.stdout.strip() == "", "printed a result without a chip"
    assert "not 'tpu'" in r.stderr


def test_multichip_needs_four_devices():
    with pytest.raises(AssertionError, match="needs 16 devices"):
        smoke.multichip_phase(
            lambda: smoke.build_net(**TOY), vocab=64, seq_len=16,
            n_chips=16, global_batch=16, parity_batch=16, steps=1,
            loss_tol=1, batch_buckets=(1,), seq_buckets=(16,),
            lengths=(16,), serve_tol=1, workdir="/nonexistent")
    assert len(jax.devices()) < 16
