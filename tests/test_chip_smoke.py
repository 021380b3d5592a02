"""chip_smoke.py's own code, driven on the CPU at 2L/d128: the same section
functions the chip runs at flagship width (flash in interpret mode; the int8
gate in the flagship's bf16), and the script's refusal to report anything on
a machine with no TPU."""
import dataclasses
import importlib
import os
import sys

import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.models import transformer
from deeplearning4j_tpu.models.transformer import TransformerConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def _cfg(max_len=128, dtype=jnp.float32):
    return TransformerConfig(vocab_size=512, n_layers=2, n_heads=4,
                             d_model=128, max_len=max_len, dtype=dtype,
                             fused_qkv=True)


@pytest.fixture(scope="module")
def trained():
    return chip_smoke.train_section(_cfg(), batch=4, steps=3)


def test_train_section(trained):
    _model, _params, losses = trained
    assert len(losses) == 3 and losses[-1] < losses[0]


def test_serve_section_over_real_sockets(trained):
    model, params, _ = trained
    chip_smoke.serve_section(
        model, params, prompt_lens=(12, 28, 60, 100, 20, 40, 90, 7),
        new_tokens=8, slots=4)


def test_serve_section_fails_on_a_wrong_logit_tolerance(trained, monkeypatch):
    """The checks are live: a tolerance no arithmetic can meet fails the
    section (and its shutdown still runs, or this test would hang)."""
    model, params, _ = trained
    monkeypatch.setitem(chip_smoke.LOGIT_TOL, "float32", -1.0)
    with pytest.raises(chip_smoke.SmokeFailure, match="disagree"):
        chip_smoke.serve_section(
            model, params, prompt_lens=(12, 28, 60, 100), new_tokens=4,
            slots=4, stream_idx=(1,))


def test_quant_gate_section_bf16():
    """The int8 gate at the flagship's serving dtype: bf16 compute on both
    sides of the comparison must not trip it."""
    import jax
    bf16 = transformer.TransformerLM(dataclasses.replace(
        _cfg(dtype=jnp.bfloat16), n_layers=1))      # the gate runs eagerly
    chip_smoke.quant_gate_section(bf16, bf16.init_params(jax.random.key(0)))


def test_flash_section_interpret_mode(monkeypatch):
    """The flash arm runs the Pallas kernel (interpret mode off-TPU, so no
    Mosaic call to require) and agrees with the XLA-attention arm."""
    calls = []
    # the package re-exports the function under the module's name
    fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")
    real = fa._fwd_pallas
    monkeypatch.setattr(
        fa, "_fwd_pallas",
        lambda *a: (calls.append(a[-1]), real(*a))[1])
    monkeypatch.setattr(transformer, "FLASH_ATTENTION", True)
    chip_smoke.flash_section(
        dataclasses.replace(_cfg(max_len=64), n_layers=1), batch=2,
        require_mosaic=False)
    assert calls and all(interpret is True for interpret in calls)


@pytest.mark.slow
def test_multichip_section_on_the_virtual_mesh(trained):
    """Section F's code path (never its claim: only real chips count)."""
    _model, _params, losses = trained
    chip_smoke.multichip_section(_cfg(), one_chip_losses=losses, batch=4,
                                 steps=2)


def test_main_refuses_without_a_tpu(capsys):
    """On a CPU-only machine ``main()`` (what the script exits with) is
    non-zero, and it prints no result line."""
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err and out == ""
