"""The benchmark's cell ``laguna-codegen`` exists and runs: ``BENCHMARK.json``
names its configuration and the cell, every file those names lead to is there
and loads through ``perfbench.harness.Cell``, the cost functions count what
the weights' shapes say (3.870 G at the published sizes), the cell stands in
the ``workloads`` of every accepted metric ISSUE 43 lists and of its own
five, and the cell's rehearsal run through ``perfbench/run.py`` ends
``correct`` on the CPU. A ``model_config`` PR that brings files under
``perfbench/`` and no entry (PR 37, refused ``config_not_added``) fails the
first test here."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

CONFIG = "laguna-xs2-33b-a3b-stage5"
CELL = "laguna-codegen"
MIX = "codegen-closed-128"
#: the accepted metrics the cell reports, in the benchmark's order
ACCEPTED = ["serve_tok_s", "setup_s", "slots_active_mean",
            "decode_step_p50_ms.tput", "compiles_in_window.tput",
            "idle_pct.tput", "loop_host_ms_p50.tput", "live_tokens_mean.tput",
            "kv_move_dev_pct.tput", "unscoped_dev_pct.tput",
            "moe_dev_pct.tput", "experts_touched_mean.tput",
            "moe_roofline_pct.tput", "decode_touched_roofline_pct",
            "prefill_stall_pct.tput", "join_ms_per_ktok.tput",
            "join_fetch_share_pct.tput", "join_max_ms.tput",
            "joins_per_admit_mean.tput"]
OWN = ["attn_window_dev_pct.tput", "attn_full_dev_pct.tput",
       "attn_window_roofline_pct.tput", "attn_full_roofline_pct.tput",
       "cache_bytes_per_live_token.tput"]
#: not this cell's: after B0 (ROADMAP), the fourth family's reader, and the
#: holds' reader where it finds none (PERF.md section 7)
NOT_LISTED = ["idle_named_pct.tput", "gqa_roofline_pct.tput"]


def test_benchmark_json_has_the_configuration_and_the_cell():
    bench = harness.benchmark()
    conf = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(conf) == 1
    assert conf[0] == {
        "name": CONFIG, "file": f"perfbench/configs/{CONFIG}.json",
        "reduced": ["num_hidden_layers"], "why": conf[0]["why"],
        "source":
        "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"}
    row = [w for w in bench["workloads"] if w["name"] == CELL]
    assert row == [{"name": CELL, "config": CONFIG, "traffic": MIX,
                    "chips": 1, "why": row[0]["why"]}]
    assert all(1 <= len(x["why"]) <= 200 for x in (conf[0], row[0]))
    # appended behind the five configurations and seven cells there were
    assert [c["name"] for c in bench["configs"]].index(CONFIG) == 5
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 7
    reported = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
                if CELL in m.get("workloads", [CELL])]
    assert reported == ACCEPTED + OWN
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in NOT_LISTED:
            assert CELL not in m["workloads"]
    # the five new metrics: appended together, one cell, one metric moved
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(OWN[0])
    assert names[at:at + 5] == OWN
    assert [(m["source"], m["layer"], m["better"], m["unit"])
            for m in bench["per_layer"][at:at + 5]] == [
        ("device_trace", "model", "lower", "%"),
        ("device_trace", "model", "lower", "%"),
        ("device_trace", "kernels", "higher", "%"),
        ("device_trace", "kernels", "higher", "%"),
        ("program_span", "engine", "lower", "bytes")]
    # one cell when they came; only later PRs' cells stand behind it (PR
    # 46's ``phi4flash-reasoning`` reports all five)
    assert all(m["workloads"] in ([CELL], [CELL, "phi4flash-reasoning"])
               and m["moves"] == "serve_tok_s"
               for m in bench["per_layer"][at:at + 5])
    # one four-chip cell in eight: inside the quarter the contract allows
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
        for name in ("n_params", "kv_row_bytes", "moe_step_bytes",
                     "attn_full_cache_bytes", "attn_window_cache_bytes",
                     "decode_touched_bytes", "decode_step_bytes",
                     "decode_step_flops", "train_flops_per_token"):
            assert callable(getattr(cell.costs, name))
        assert callable(cell.runner.run)
        assert [m["name"] for m in cell.per_layer] == ACCEPTED[2:] + OWN
        for m in cell.per_layer:
            reader = harness.load_module("layer_metrics", m["name"] + ".py")
            assert callable(reader.read), m["name"]
        for key in ("served_logit_gap_max", "served_logit_gap_mean"):
            assert cell.limit(key) > 0
        assert cell.model.build_model(cell.config).config.n_layers \
            == cell.config["num_hidden_layers"] == 5


def test_the_mix_is_the_issues_letter_for_letter():
    tr = harness.load_json("traffic", MIX + ".json")
    want = {
        "kind": "serve-closed", "slots": 64, "clients": 128,
        "queue_limit": 256, "max_inflight": 512, "deploy_warmup": False,
        "prefill_buckets": [2048, 4096],
        "prompt_len": {"dist": "lognormal", "median": 2048, "sigma": 0.5,
                       "min": 1024, "max": 4096},
        "output_len": {"dist": "uniform", "min": 1024, "max": 3072},
        "max_total": 7168, "cache_pages": 7169, "ramp_s": 15, "trace_s": 4,
        "check_requests": 4, "plan_requests": 1024, "span_ring": 1048576}
    assert {k: tr[k] for k in want} == want
    assert "MAKES NEITHER" in tr["output_note"]
    cfg = harness.load_json("configs", CONFIG + ".json")
    # the longest request fits a slot, and every slot's pages the pool
    assert tr["prompt_len"]["max"] + tr["output_len"]["max"] \
        == tr["max_total"] == cfg["n_positions"]
    assert tr["cache_pages"] == tr["slots"] * cfg["n_positions"] // 64 + 1
    # every prompt is longer than the window: every live slot's ring is full
    assert tr["prompt_len"]["min"] > cfg["sliding_window"]
    assert tr["rehearsal"]["prompt_len"]["min"] \
        > cfg["rehearsal"]["sliding_window"]
    # the deployment's own 2 tokens an expert a step
    assert tr["slots"] * cfg["num_experts_per_tok"] / cfg["num_experts"] == 2


def test_the_configuration_states_its_cut():
    cfg = harness.load_json("configs", CONFIG + ".json")
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
        "intermediate_size": 8192, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128, "rms_norm_eps": 1e-6,
        "num_experts": 256, "num_experts_per_tok": 8,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "gating": True, "sliding_window": 512, "partial_rotary_factor": 0.5,
        "moe_routed_scaling_factor": 2.5, "attention_bias": False,
        "tie_word_embeddings": False, "max_position_embeddings": 262144}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert (cfg["num_hidden_layers"], cfg["num_hidden_layers_published"]) \
        == (5, 40)
    # the published lists whole; the layers run are their first five entries
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert len(cfg[key]) == 40 and cfg["layers_run"][key] == cfg[key][:5]
    assert cfg["layers_run"]["layer_types"] == [
        "full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["layers_run"]["num_attention_heads_per_layer"] == [
        48, 64, 64, 64, 48]
    assert cfg["layers_run"]["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert cfg["rope_parameters"]["full_attention"] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
    assert "8 pipeline stages" in cfg["deployment"]
    for key in ("gating", "router", "layer", "rope_pairs", "window",
                "n_positions", "dtypes", "weights", "parameters"):
        assert cfg["assumed"][key], key
    assert len(cfg["departures"]) >= 3
    assert {"hidden_size", "sliding_window", "num_experts", "vocab_size",
            "rope_parameters", "num_attention_heads_per_layer"} \
        <= set(cfg["rehearsal"])


def test_costs_count_the_weights_shapes():
    cell = harness.Cell(CELL)
    shapes = cell.model.weight_shapes(cell.config)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)
            if len(a.shape) >= 2)
    assert n == cell.costs.n_params(cell.config) == 3869835264
    assert abs(n / 3.870e9 - 1) < 0.0005
    # bfloat16 as held: 7.74 GB
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree.leaves(shapes))
    assert abs(held / 7.74e9 - 1) < 0.001
    c, cfg = cell.costs, cell.config
    # the issue's arithmetic, re-derived: the parts of the 3.870 G
    assert c.attention_params(cfg, 48) == 29458432      # 29.46 M
    assert c.attention_params(cfg, 64) == 37879808      # 37.88 M
    assert c.dense_params(cfg) == 50331648              # 50.33 M
    assert c.expert_params(cfg) == 3145728              # 3.146 M
    assert 2 * cfg["vocab_size"] * cfg["hidden_size"] == 411041792
    # and the whole model by the same counts: the published "33.4B"
    whole = (411041792 + 39 * (256 * 3145728 + 3145728 + 2048 * 256)
             + 50331648 + 10 * 29458432 + 30 * 37879808)
    assert abs(whole / 33.44e9 - 1) < 0.001
    # a decode step at the cell's size, by bytes: the issue's 8.7 GB
    step = c.decode_touched_bytes(cfg, 4 * 221, 64, 64 * 3400)
    assert abs(step / 8.7e9 - 1) < 0.03
    assert c.kv_row_bytes(cfg) == 4096
    assert c.attn_full_cache_bytes(cfg, 217600, 64) == 2 * 4096 * 217664
    assert c.attn_window_cache_bytes(cfg, 64 * 512, 64) \
        == 3 * 4096 * (32768 + 64)
    with pytest.raises(NotImplementedError):
        c.train_flops_per_token(cfg, 1024)


def test_the_cell_rehearses_through_run_py():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 43), "--seconds", "4", "--trace", "0",
         "--rehearsal"], capture_output=True, text=True, env=env, timeout=280)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 10
    assert line["reported"] == ["serve_tok_s", "setup_s"]
    assert "BENCH_RUN" not in out.stdout


@pytest.mark.parametrize("name", OWN)
def test_a_program_without_the_new_names_reports_no_new_metric(name):
    """What the parent commit's program gives the new readers: a context
    with no trace and spans without the new attributes. Nothing is read and
    nothing raises, so the line leaves the metric out."""
    cell = harness.Cell(CELL, rehearsal=True)
    reader = harness.load_module("layer_metrics", name + ".py")
    ctx = {"cell": cell, "device": {"kind": "cpu"}, "trace": None,
           "trace_span": (0.0, 1.0), "spans": []}
    assert reader.read(ctx) is None


def test_cache_bytes_per_live_token_reads_the_steps_own_books():
    class Span:
        def __init__(self, name, **attrs):
            self.name, self.attrs, self.ts_us, self.dur_us = name, attrs, 0, 1

    reader = harness.load_module("layer_metrics",
                                 "cache_bytes_per_live_token.tput.py")
    ctx = {"spans": [Span("decode_step", cache_bytes=10240, live_tokens=1),
                     Span("decode_step", cache_bytes=40960, live_tokens=4),
                     Span("decode_step", live_tokens=4),      # the parent's
                     Span("prefill", cache_bytes=1, live_tokens=1)]}
    assert reader.read(ctx) == 10240.0
