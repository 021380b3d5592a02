"""The benchmark's cell ``phi4flash-reasoning`` exists and runs:
``BENCHMARK.json`` names its configuration and the cell, every file those
names lead to is there and loads through ``perfbench.harness.Cell``, the cost
functions count what the weights' shapes say (3,852.6 M at the published
sizes, ISSUE 46's arithmetic part by part), the cell stands in the
``workloads`` of every accepted metric ISSUE 46 lists and of its own three,
each new reader returns None on a program without its names, the float8
control fails the rehearsal limits, the cell's rehearsal run through
``perfbench/run.py`` ends ``correct`` on the CPU, and the rehearsal's spans
carry ``page_readers`` and ``tail_rows``. A ``model_config`` PR that brings
files under ``perfbench/`` and no entry (PR 37, refused ``config_not_added``)
fails the first test here."""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

CONFIG = "phi-4-mini-flash-reasoning"
CELL = "phi4flash-reasoning"
MIX = "reasoning-closed-128"
SOURCE = ("https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/"
          "blob/main/config.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: the accepted metrics the cell reports, in the benchmark's order
ACCEPTED = ["serve_tok_s", "setup_s", "slots_active_mean",
            "decode_step_p50_ms.tput", "compiles_in_window.tput",
            "decode_roofline_pct", "idle_pct.tput", "loop_host_ms_p50.tput",
            "live_tokens_mean.tput", "kv_move_dev_pct.tput",
            "unscoped_dev_pct.tput", "ssm_dev_pct.tput",
            "ssm_roofline_pct.tput", "dense_ffn_dev_pct.tput",
            "prefill_stall_pct.tput",
            "join_ms_per_ktok.tput", "join_fetch_share_pct.tput",
            "join_max_ms.tput", "joins_per_admit_mean.tput",
            "attn_window_dev_pct.tput", "attn_full_dev_pct.tput",
            "attn_window_roofline_pct.tput", "attn_full_roofline_pct.tput",
            "cache_bytes_per_live_token.tput"]
OWN = ["attn_cross_dev_pct.tput", "attn_cross_roofline_pct.tput",
       "gmu_dev_pct.tput"]
#: not this cell's: no routed experts, no latent or KDA layer, the fourth
#: family's one-layer roofline, and the holds' readers where they find none
NOT_LISTED = ["dense_ffn_roofline_pct.tput",    # reads 128 here: PERF.md
              "moe_dev_pct.tput", "experts_touched_mean.tput",
              "moe_roofline_pct.tput", "decode_touched_roofline_pct",
              "mla_dev_pct.tput", "mla_roofline_pct.tput",
              "kda_state_dev_pct.tput", "kda_state_roofline_pct.tput",
              "gqa_roofline_pct.tput", "idle_named_pct.tput",
              "join_hold_dev_pct.tput", "zero_pairs_pct.tput"]


def test_benchmark_json_has_the_configuration_and_the_cell():
    bench = harness.benchmark()
    conf = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert conf == [{"name": CONFIG, "source": SOURCE,
                     "file": f"perfbench/configs/{CONFIG}.json",
                     "reduced": [], "why": conf[0]["why"]}]
    row = [w for w in bench["workloads"] if w["name"] == CELL]
    assert row == [{"name": CELL, "config": CONFIG, "traffic": MIX,
                    "chips": 1, "why": row[0]["why"]}]
    assert all(1 <= len(x["why"]) <= 200 for x in (conf[0], row[0]))
    # appended behind the six configurations and eight cells there were
    assert [c["name"] for c in bench["configs"]].index(CONFIG) == 6
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 8
    reported = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
                if CELL in m.get("workloads", [CELL])]
    assert reported == ACCEPTED + OWN
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in NOT_LISTED:
            assert CELL not in m["workloads"], m["name"]
        elif "workloads" in m and CELL in m["workloads"]:
            assert m["workloads"][-1] == CELL       # appended, not inserted
    # the three new metrics: appended together, one cell, one metric moved
    assert [m["name"] for m in bench["per_layer"][-3:]] == OWN
    assert [(m["source"], m["layer"], m["better"], m["unit"])
            for m in bench["per_layer"][-3:]] == [
        ("device_trace", "model", "lower", "%"),
        ("device_trace", "kernels", "higher", "%"),
        ("device_trace", "model", "lower", "%")]
    assert all(m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
               for m in bench["per_layer"][-3:])
    # one four-chip cell in nine: inside the quarter the contract allows
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert len(json.dumps(bench, indent=1)) < 64 * 1024


def test_every_file_the_cells_name_leads_to_loads():
    for rehearsal in (False, True):
        cell = harness.Cell(CELL, rehearsal=rehearsal)
        assert cell.chips == 1 and cell.traffic["kind"] == "serve-closed"
        assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s",
                                                        "setup_s"}
        for name in ("build_model", "make_weights", "weight_shapes"):
            assert callable(getattr(cell.model, name))
        for name in ("next_token_gaps", "next_token_argmax", "logits"):
            assert callable(getattr(cell.reference, name))
        for name in ("n_params", "kv_row_bytes", "ssm_step_bytes",
                     "attn_full_cache_bytes", "attn_window_cache_bytes",
                     "attn_cross_cache_bytes", "dense_ffn_step_bytes",
                     "decode_step_bytes", "decode_step_flops",
                     "train_flops_per_token"):
            assert callable(getattr(cell.costs, name))
        assert callable(cell.runner.run)
        assert [m["name"] for m in cell.per_layer] == ACCEPTED[2:] + OWN
        for m in cell.per_layer:
            reader = harness.load_module("layer_metrics", m["name"] + ".py")
            assert callable(reader.read), m["name"]
        for key in ("served_logit_gap_max", "served_logit_gap_mean"):
            assert cell.limit(key) > 0
        assert cell.model.build_model(cell.config).config.n_layers \
            == cell.config["num_hidden_layers"] == (12 if rehearsal else 32)


def test_the_reference_imports_nothing_of_the_program():
    for part in ("reference", "costs"):
        with open(os.path.join(harness.HERE, part, "phi4flash.py")) as f:
            text = f.read()
        assert "deeplearning4j_tpu" not in text.replace(
            "perfbench/models/phi4flash.py", "")
        assert "import perfbench" not in text and "from perfbench" not in text
    with open(os.path.join(harness.HERE, "reference", "phi4flash.py")) as f:
        text = f.read()
    assert "Precision.HIGHEST" in text and "lax.scan(step" in text


def test_the_mix_is_the_issues_letter_for_letter():
    tr = harness.load_json("traffic", MIX + ".json")
    want = {
        "kind": "serve-closed", "slots": 64, "clients": 128,
        "queue_limit": 256, "max_inflight": 512, "deploy_warmup": False,
        "prefill_buckets": [2048, 4096],
        "prompt_len": {"dist": "lognormal", "median": 2048, "sigma": 0.5,
                       "min": 1024, "max": 4096},
        # ISSUE 46's change (a), made on two sets' reading (``output_note``)
        "output_len": {"dist": "uniform", "min": 1536, "max": 4096},
        "max_total": 8192, "cache_pages": 8193, "trace_s": 4,
        "check_requests": 4, "plan_requests": 160, "span_ring": 1048576}
    assert {k: tr[k] for k in want} == want
    assert tr["ramp_s"] in (20, 25, 30) and str(tr["ramp_s"]) in tr[
        "ramp_note"]
    assert "MAKES (a)" in tr["output_note"] and "NOT (b)" in tr["output_note"]
    cfg = harness.load_json("configs", CONFIG + ".json")
    # the longest request fits a slot, and every slot's pages the pool
    assert tr["prompt_len"]["max"] + tr["output_len"]["max"] \
        == tr["max_total"] == cfg["n_positions"]
    assert tr["cache_pages"] == tr["slots"] * cfg["n_positions"] \
        // cfg["page_tokens"] + 1
    # every prompt is longer than the window: every live slot's ring is full
    assert tr["prompt_len"]["min"] > cfg["sliding_window"]
    assert tr["rehearsal"]["prompt_len"]["min"] \
        > cfg["rehearsal"]["sliding_window"]
    assert tr["rehearsal"]["ramp_s"] == 2


def test_every_plan_has_requests_that_end_inside_the_window():
    """``ramp_note`` and ``plan_note``: a run in whose window no request
    ends has nothing to compare with the reference and reads ``correct``
    false. The generator hands out the quantiles of the length
    distributions, shuffled by the seed, so what the first 64 requests are
    can be read off the plan: on every seed some have an output short enough
    to end before the window closes (about 1,720 steps for the slot that
    joined last, my chip runs, PR 46), and never more requests are sent than
    the plan holds."""
    from perfbench import loadgen
    tr = harness.load_json("traffic", MIX + ".json")
    n, slots = tr["plan_requests"], tr["slots"]
    fewest, most = slots, 0
    for seed in range(2**31, 2**31 + 300):
        rng = np.random.default_rng([seed, 23])
        loadgen.lengths(tr["prompt_len"], n, rng)       # drawn first
        first = loadgen.lengths(tr["output_len"], n, rng)[:slots]
        fewest = min(fewest, int((first <= 1720).sum()))
        most = max(most, int((first <= 1950).sum()))
    assert fewest >= 1
    assert tr["clients"] + most <= n


def test_the_configuration_is_the_published_one_uncut():
    cfg = harness.load_json("configs", CONFIG + ".json")
    assert cfg["source"] == SOURCE and cfg["reduced"] == []
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = [r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning"][0]
        assert row["source_url"] == SOURCE
        assert {k: cfg[k] for k in row["config"]} == row["config"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"], cfg["hidden_size"],
            cfg["intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["sliding_window"],
            cfg["mb_per_layer"]) == (32, 200064, 2560, 10240, 40, 20, 512, 2)
    assert (cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_expand"],
            cfg["mamba_d_inner"], cfg["mamba_dt_rank"]) == (16, 4, 2, 5120,
                                                            160)
    assert (cfg["compute_dtype"], cfg["param_dtype"], cfg["state_dtype"],
            cfg["n_positions"], cfg["page_tokens"]) == (
        "bfloat16", "bfloat16", "float32", 8192, 64)
    for key in ("layers", "mamba", "differential_attention", "memory_unit",
                "prefill", "n_positions", "page_tokens", "parameters",
                "dtypes", "weights"):
        assert cfg["assumed"][key], key
    assert "3,852.6 M" in cfg["assumed"]["parameters"]
    assert len(cfg["departures"]) >= 5
    assert any("greedy" in d and "eos" in d for d in cfg["departures"])
    assert {"hidden_size", "sliding_window", "num_hidden_layers",
            "vocab_size", "mamba_d_inner", "mamba_d_state"} \
        <= set(cfg["rehearsal"])
    assert cfg["rehearsal"]["num_hidden_layers"] >= 8
    assert cfg["rehearsal"]["sliding_window"] == 8


def test_costs_are_the_issues_arithmetic():
    cell = harness.Cell(CELL)
    shapes = cell.model.weight_shapes(cell.config)
    leaves = jax.tree.leaves(shapes)
    n = sum(int(np.prod(a.shape)) for a in leaves)
    assert n == 3852562960 and abs(n / 3852.6e6 - 1) < 1e-4
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)
    assert abs(held / 7.71e9 - 1) < 0.001
    c, cfg = cell.costs, cell.config
    # LayerNorm gains and biases (65 norms) and the 16 lambda_init scalars
    # are left out of the costs' count
    assert c.n_params(cfg) == n - 65 * 2 * 2560 - 16
    assert c.kinds(cfg) == cell.reference.layers(cfg)
    assert [c.n_layers(cfg, k) for k in ("mamba", "window", "full", "memory",
                                         "cross")] == [9, 8, 1, 7, 7]
    # the issue's parts
    assert c.ffn_params(cfg) == 78643200                    # 78.64 M
    assert c.attention_params(cfg, True) == 19668864        # 19.67 M
    assert c.attention_params(cfg, False) == 13112704        # 13.11 M
    assert abs(c.mamba_params(cfg) / 41.24e6 - 1) < 5e-4
    assert c.memory_params(cfg) == 26214400                 # 26.21 M
    assert 200064 * 2560 == 512163840                       # 512.2 M
    assert c.kv_row_bytes(cfg) == 5120 and c.page_readers(cfg) == 8
    assert c.slot_state_bytes(cfg) == 20971520 + 3225600
    # a decode step at 64 slots and 3.5 k live rows a slot
    live = 64 * 3500
    assert c.attn_full_cache_bytes(cfg, live, 64) == 5120 * (live + 64)
    assert c.attn_cross_cache_bytes(cfg, live, 64) == 7 * 5120 * live
    assert abs((c.attn_full_cache_bytes(cfg, live, 0)
                + c.attn_cross_cache_bytes(cfg, live, 0)) / 9.2e9 - 1) < 0.01
    assert c.attn_window_cache_bytes(cfg, 64 * 512, 64) \
        == 8 * 5120 * (32768 + 64)
    assert abs(c.attn_window_cache_bytes(cfg, 64 * 512, 0) / 1.34e9 - 1) \
        < 0.01
    assert abs((c.ssm_step_bytes(cfg, 64)
                - 2 * 9 * c.mamba_params(cfg)) / 0.41e9 - 1) < 0.02
    assert abs(c.dense_ffn_step_bytes(cfg) / 5.03e9 - 1) < 0.002
    # what the whole step's share divides by: the weights once, the live
    # rows eight times, no ring and no state
    assert c.decode_step_bytes(cfg, live) \
        == 2 * c.n_params(cfg) + 8 * 5120 * live
    floor_ms = 1e3 * (c.decode_step_bytes(cfg, live)
                      + c.attn_window_cache_bytes(cfg, 64 * 512, 64)
                      + 2.0 * 64 * 9 * c.mamba_state_bytes(cfg)) / 819e9
    assert 22.0 < floor_ms < 24.0                           # "near 23 ms"
    assert c.decode_step_flops(cfg, live, 64) / 197e12 < 0.3 * floor_ms / 1e3
    with pytest.raises(NotImplementedError):
        c.train_flops_per_token(cfg, 1024)


def test_the_cell_rehearses_through_run_py():
    """The mix's rehearsal takes a 2 s ramp (``ramp_note``) and this test
    the longest time-out a test's own limit of 300 s allows, seven times
    what the run takes alone (41 s, PR 46): tier-1 runs six workers."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 46), "--seconds", "4", "--trace", "0",
         "--rehearsal"], capture_output=True, text=True, env=env, timeout=280)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True, \
        out.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 10
    assert line["reported"] == ["serve_tok_s", "setup_s"]
    assert "BENCH_RUN" not in out.stdout


def test_the_rehearsals_spans_carry_the_readers_and_the_tail_rows():
    """The served path at the rehearsal sizes, in this process: every
    ``decode_step`` span says that three layers read the pages (the shared
    layer and the two query-only ones of 12), every ``prefill`` span that
    the upper layers computed one row."""
    from deeplearning4j_tpu.observability.tracing import global_trace_sink
    from perfbench import serving
    cell = harness.Cell(CELL, rehearsal=True)
    jax.config.update("jax_enable_compilation_cache", False)
    out = serving.run(cell, 4600046, 3.0, False,
                      harness.device_info(1, True), time.time())
    assert out["failed"] == 0 and out["attempted"] > 10
    spans = global_trace_sink().spans()
    steps = [s for s in spans if s.name == "decode_step"
             and "live_tokens" in (s.attrs or {})]
    joins = [s for s in spans if s.name == "prefill"]
    assert len(steps) > 100 and len(joins) > 10
    assert {s.attrs["page_readers"] for s in steps} == {3}
    assert {s.attrs["tail_rows"] for s in joins} == {1}
    assert {s.attrs["bucket"] for s in joins} <= {16, 32, 64}
    # ONE paged layer: a page of 64 rows of 2 x 2 x 16 bfloat16 numbers
    reader = harness.load_module("layer_metrics",
                                 "cache_bytes_per_live_token.tput.py")
    per_token = reader.read({"spans": steps})
    model = cell.model.build_model(cell.config)
    assert model.page_bytes(64) == 64 * 128
    assert per_token < 128 + model.slot_state_bytes() / 12


@pytest.mark.parametrize("name", OWN)
def test_a_program_without_the_new_names_reports_no_new_metric(name):
    """What the parent commit's program gives the new readers: no trace, a
    trace whose operations carry none of the new names, spans without the
    new attributes. Nothing is read and nothing raises, so the line leaves
    the metric out."""
    cell = harness.Cell(CELL, rehearsal=True)
    reader = harness.load_module("layer_metrics", name + ".py")

    class Span:
        name, attrs, ts_us, dur_us = "decode_step", {"live_tokens": 9,
                                                     "active": 2}, 0, 1

    class Trace:
        clock_offset = 0.0

        def module_durations(self, _pattern):
            return [0.0] * 4

    base = {"cell": cell, "device": {"kind": "cpu"}, "trace_span": (0.0, 1.0),
            "spans": [Span()]}
    assert reader.read({**base, "trace": None}) is None
    # the parent's decode program: no operation under one of the new names
    assert reader.read({**base, "trace": Trace(),
                        "_phi4flash": None}) is None
    helper = harness.load_module("layer_metrics", "_phi4flash.py")
    assert helper.inner_of(
        "jit(_decode_paged)/attn_core/gqa_attend/pallas_call") is None


def test_the_new_readers_sum_the_named_operations():
    from perfbench.layer_metrics import _phi4flash
    path = ("jit(_decode_paged)/attn_core/xattn_attend/"
            "jit(_paged_grouped_attention)/pallas_call")
    assert _phi4flash.inner_of(path) == "xattn_attend"
    assert _phi4flash.inner_of(
        "jit(_decode_paged)/attn_core/xattn_attend/attn_diff/sub") \
        == "xattn_attend"
    assert _phi4flash.inner_of("jit(_decode_paged)/attn_out/gmu/dot") == "gmu"
    acc = {"xattn_attend": 3.0, "xattn_proj": 1.0, "gmu": 2.0, None: 14.0}

    class Trace:
        def module_durations(self, _pattern):
            return [0.0] * 4

    ctx = {"_phi4flash": acc, "trace": Trace()}
    assert _phi4flash.share_pct(ctx, _phi4flash.CROSS) == 20.0
    assert _phi4flash.share_pct(ctx, ("gmu",)) == 10.0
    assert _phi4flash.seconds_a_step(ctx, ("xattn_attend",)) == 0.75


def test_the_program_writes_the_names_where_the_readers_look():
    """The decode program lowered at the rehearsal sizes: its operations'
    ``op_name`` paths hold the new names, each inside the vocabulary's scope
    for that part of the block, and the Mamba-1 parts the accepted ones."""
    from deeplearning4j_tpu.models.generation import DecodeEngine
    cell = harness.Cell(CELL, rehearsal=True)
    cfg = cell.config
    model, shapes = cell.model.build_model(cfg), cell.model.weight_shapes(cfg)
    eng = DecodeEngine(model, shapes, max_len=cfg["n_positions"],
                       prefill_buckets=[16], page_tokens=8)
    cache = jax.eval_shape(lambda: model.new_paged_cache(4, 9, 8))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)     # noqa: E731
    text = eng._decode_paged_jit.trace(
        shapes, cache, i32(4, 16), i32(4), i32(4), i32()).lower(
        ).as_text(debug_info=True)
    for path in ("attn_qkv/xattn_proj/", "attn_core/xattn_attend/",
                 "attn_core/xattn_attend/attn_diff/",
                 "attn_core/gqa_attend/attn_diff/",
                 "attn_core/swa_attend/attn_diff/", "attn_out/xattn_proj/",
                 "attn_qkv/gmu/", "attn_core/gmu/", "attn_out/gmu/",
                 "attn_qkv/ssm_proj/", "attn_qkv/ssm_conv/",
                 "attn_core/ssm_state/", "attn_out/ssm_out/",
                 "kv_write/swa_write/", "mlp/ffn_dense/"):
        assert path in text, path


def test_the_float8_control_fails_the_rehearsal_limits():
    """Greedy tokens from the engine's own prefill and decode programs at the
    rehearsal size (bfloat16, as the configuration states), 400 of them over
    two seeds; the float8 control teacher-forced over the same prompts and
    tokens. The program's served tokens stay inside the rehearsal limits on
    the reference's logits, the control's first choices do not."""
    from deeplearning4j_tpu.models.generation import DecodeEngine
    from perfbench import serving
    cell = harness.Cell(CELL, rehearsal=True)
    cfg = cell.config
    gaps, low_gaps = [], []
    for seed in (31, 32):
        params = cell.model.make_weights(cfg, seed)
        engine = DecodeEngine(cell.model.build_model(cfg), params,
                              max_len=cfg["n_positions"],
                              prefill_buckets=[16])
        rng = np.random.default_rng(seed)
        sample = []
        for _ in range(4):
            prompt = rng.integers(0, cfg["vocab_size"], 16).astype(np.int32)
            toks = np.asarray(engine.generate(prompt[None], 50))[0]
            sample.append({"prompt": prompt.tolist(),
                           "tokens": toks.tolist()})
        gaps.append(serving.served_token_gaps(cell, params, sample))
        seqs, _cands, mask = serving.pack(sample, cfg["n_positions"])
        low = np.asarray(cell.reference.next_token_argmax(
            params, jnp.asarray(seqs), cfg, True))
        low_gaps.append(np.asarray(cell.reference.next_token_gaps(
            params, jnp.asarray(seqs), jnp.asarray(low), cfg))[mask])
    gaps, low_gaps = np.concatenate(gaps), np.concatenate(low_gaps)
    assert gaps.size == 400
    assert gaps.max() <= cell.limit("served_logit_gap_max")
    assert gaps.mean() <= cell.limit("served_logit_gap_mean")
    assert low_gaps.max() > cell.limit("served_logit_gap_max")
    assert low_gaps.mean() > cell.limit("served_logit_gap_mean")
