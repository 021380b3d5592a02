"""The plain reference against the program at 2 layers, d 128, float32, on
the CPU: logits against ``TransformerLM.apply``, and three AdamW steps (loss,
first gradient, parameters' change) against ``make_train_step``."""
import jax
import jax.numpy as jnp
import numpy as np
import optax

from perfbench import harness

HP = dict(lr=3e-4, b1=0.9, b2=0.999, eps=1e-8, weight_decay=1e-4)


def _tiny():
    cfg = harness.load_json("configs", "gpt2-medium.json")
    cfg.update(cfg["rehearsal"])
    cfg["compute_dtype"] = "float32"
    return cfg


def test_logits_match_apply():
    cfg = _tiny()
    mod = harness.load_module("models", "gpt2.py")
    ref = harness.load_module("reference", "gpt2.py")
    w = mod.make_weights(cfg, 2**31 + 3)
    toks = np.random.default_rng(0).integers(0, 512, (3, 128)).astype(np.int32)
    prog = np.asarray(jax.jit(mod.build_model(cfg).apply)(w, toks))
    mine = np.asarray(ref.logits(w, jnp.asarray(toks), cfg))
    assert np.abs(prog).max() > 0.5
    assert np.abs(prog - mine).max() < 1e-5     # float32 against float32


def test_three_adamw_steps_match_the_train_step():
    cfg = _tiny()
    mod = harness.load_module("models", "gpt2.py")
    ref = harness.load_module("reference", "gpt2.py")
    w = mod.make_weights(cfg, 9)
    rng = np.random.default_rng(1)
    batches = []
    for _ in range(3):
        t = rng.integers(0, 512, (4, 129)).astype(np.int32)
        batches.append((t[:, :-1], t[:, 1:]))
    out = ref.train_steps(w, batches, cfg, HP, rows=2)
    opt = optax.adamw(3e-4)
    p = jax.tree.map(jnp.copy, w)
    st = jax.jit(opt.init)(p)
    step = mod.build_model(cfg).make_train_step(opt)
    losses = []
    for i, (t, g) in enumerate(batches):
        p, st, loss = step(p, st, jnp.asarray(t), jnp.asarray(g))
        losses.append(float(loss))
        if i == 0:
            grads = np.asarray(ref.leaf_norms(st[0].mu)) / 0.1
    delta = np.asarray(ref.leaf_norms(jax.tree.map(jnp.subtract, p, w)))
    assert np.allclose(losses, out["losses"], atol=2e-5)
    assert np.allclose(grads, out["grad_norms"], rtol=1e-4)
    assert np.allclose(delta, out["delta_norms"], rtol=1e-3)


def test_cost_functions_count_what_the_shapes_say():
    costs = harness.load_module("costs", "gpt2.py")
    mod = harness.load_module("models", "gpt2.py")
    for name, millions in (("gpt2-medium", 354.7), ("gpt2-large", 773.8)):
        cfg = harness.load_json("configs", name + ".json")
        n = sum(int(np.prod(a.shape))
                for a in jax.tree.leaves(mod.weight_shapes(cfg)))
        assert n == costs.n_params(cfg) and abs(n / 1e6 - millions) < 0.1
    cfg = harness.load_json("configs", "gpt2-medium.json")
    assert abs(costs.train_flops_per_token(cfg, 1024) / 1e9 - 2.279) < 0.01
    assert costs.kv_bytes_per_token(cfg) == 24 * 2 * 1024 * 2
