"""``correct`` comes out false when the timed path is broken underneath, and
the controls fail the limits. Everything but the look for a chip is the real
run: ``run.main([... "--rehearsal"])`` at the rehearsal sizes on the CPU."""
import json

import numpy as np
import pytest

from perfbench import harness, run as prun


def _last_line(capsys, argv):
    rc = prun.main(argv)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1]), out


def _argv(cell, seed, seconds):
    return ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", "0", "--rehearsal"]


def test_a_train_step_that_returns_its_state_unchanged(monkeypatch, capsys):
    from deeplearning4j_tpu.models import transformer as tfm
    real = tfm.TransformerLM.make_train_step

    def broken(self, optimizer, return_metrics=False):
        step = real(self, optimizer, return_metrics)

        def frozen(params, opt_state, tokens, targets):
            import jax
            import jax.numpy as jnp
            keep = jax.tree.map(jnp.copy, (params, opt_state))
            _p, _s, loss = step(params, opt_state, tokens, targets)
            return keep[0], keep[1], loss
        return frozen

    sound, _ = _last_line(capsys, _argv("gpt2m-train", 11, 1))
    assert sound["correct"] is True
    monkeypatch.setattr(tfm.TransformerLM, "make_train_step", broken)
    line, out = _last_line(capsys, _argv("gpt2m-train", 11, 1))
    assert line["correct"] is False
    assert any("delta_norm_gap_worst_leaf" in x and "OUTSIDE" in x
               for x in out)


def test_a_part_of_the_batch_left_out(monkeypatch, capsys):
    """The step trains on the first half of its rows only: the loss is the
    number that is there to catch it."""
    from deeplearning4j_tpu.models import transformer as tfm
    real = tfm.TransformerLM.loss_fn

    def half(self, params, tokens, targets, rng=None, with_aux=False):
        n = tokens.shape[0] // 2
        return real(self, params, tokens[:n], targets[:n], rng, with_aux)

    monkeypatch.setattr(tfm.TransformerLM, "loss_fn", half)
    line, out = _last_line(capsys, _argv("gpt2m-train", 12, 1))
    assert line["correct"] is False
    assert any("loss_gap_step1" in x and "OUTSIDE" in x for x in out)


def test_a_served_token_altered_where_it_is_produced(monkeypatch, capsys):
    from deeplearning4j_tpu.models import generation as gen
    real = gen.sample_tokens

    def altered(logits, rng, sampler):
        toks = real(logits, rng, sampler)
        return (toks + (toks % 7 == 0)).astype(toks.dtype) % logits.shape[-1]

    sound, _ = _last_line(capsys, _argv("gpt2m-chat", 13, 4))
    assert sound["correct"] is True
    monkeypatch.setattr(gen, "sample_tokens", altered)
    line, out = _last_line(capsys, _argv("gpt2m-chat", 13, 4))
    assert line["correct"] is False
    assert any("served_logit_gap_max" in x and "OUTSIDE" in x for x in out)


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_the_float8_control_fails_a_training_limit(seed):
    """The reference in float8 in the program's place, at the rehearsal size
    and against the rehearsal limits: at least one number is outside, while
    the program's own are all inside (the chip's readings at the cells' own
    sizes are in PERF.md)."""
    cell = harness.Cell("gpt2m-train", rehearsal=True)
    tr = harness.load_module("runners", "train.py")
    opt, step, feed, weights = tr.build(cell, seed, None)
    keep = tr.sampled_leaves(cell, seed)
    ref = tr.reference_steps(cell, weights, feed, keep)
    low = tr.reference_steps(cell, weights, feed, keep, lowp=True)
    _p, _s, prog = tr.first_steps(cell, step, opt, weights, feed, keep)
    assert tr.compare(cell, prog, ref, keep).correct is True
    assert tr.compare(cell, low, ref, keep).correct is False


def test_worst_leaf_gap_is_of_norms_against_the_larger_base():
    tr = harness.load_module("runners", "train.py")
    ref = np.array([1.0, 2.0, 1e-9, 4.0, 3.0])
    prog = np.array([1.0, 2.2, 2e-9, 4.0, 3.0])
    # the all-but-zero leaf is held against the median leaf, not itself
    assert abs(tr._worst_leaf_gap(prog, ref) - 0.1) < 1e-9


def test_the_float8_control_fails_a_serving_limit():
    """Greedy tokens from the engine's own prefill and decode programs at the
    rehearsal size, 1800 of them over three seeds (a model this small has
    few near-ties); the float8 control teacher-forced over the same prompts
    and tokens. The program's served tokens stay inside the rehearsal limits
    on the reference's logits, the control's first choices do not."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.generation import DecodeEngine
    from perfbench import serving
    cell = harness.Cell("gpt2m-chat", rehearsal=True)
    cfg = cell.config
    gaps, low_gaps = [], []
    for seed in (31, 32, 33):
        params = cell.model.make_weights(cfg, seed)
        engine = DecodeEngine(cell.model.build_model(cfg), params,
                              max_len=cfg["n_positions"])
        rng = np.random.default_rng(seed)
        sample = []
        for _ in range(6):
            prompt = rng.integers(0, cfg["vocab_size"], 16).astype(np.int32)
            toks = np.asarray(engine.generate(prompt[None], 100))[0]
            sample.append({"prompt": prompt.tolist(),
                           "tokens": toks.tolist()})
        gaps.append(serving.served_token_gaps(cell, params, sample))
        seqs, _cands, mask = serving.pack(sample, cfg["n_positions"])
        low = np.asarray(cell.reference.next_token_argmax(
            params, jnp.asarray(seqs), cfg, True))
        low_gaps.append(np.asarray(cell.reference.next_token_gaps(
            params, jnp.asarray(seqs), jnp.asarray(low), cfg))[mask])
    gaps, low_gaps = np.concatenate(gaps), np.concatenate(low_gaps)
    assert gaps.size == 1800
    assert gaps.max() <= cell.limit("served_logit_gap_max")
    assert gaps.mean() <= cell.limit("served_logit_gap_mean")
    assert low_gaps.max() > cell.limit("served_logit_gap_max")
    assert low_gaps.mean() > cell.limit("served_logit_gap_mean")
