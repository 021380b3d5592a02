"""The benchmark's cell ``longcat-rollout`` exists and runs: ``BENCHMARK.json``
names its configuration and the cell, every file those names lead to is there
and loads through ``perfbench.harness.Cell``, the cost functions count what
the weights' shapes say, and the cell's rehearsal run through
``perfbench/run.py`` ends ``correct`` on the CPU. A ``model_config`` PR that
brings files under ``perfbench/`` and no entry (PR 37, refused
``config_not_added``) fails the first test here."""
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

CONFIG = "longcat-flash-omni-ep32share"
CELL = "longcat-rollout"
MIX = "rollout-closed-128"
#: the accepted metrics the cell reports, besides its own three
ACCEPTED = ["serve_tok_s", "setup_s", "slots_active_mean",
            "decode_step_p50_ms.tput", "compiles_in_window.tput",
            "idle_pct.tput", "loop_host_ms_p50.tput", "live_tokens_mean.tput",
            "idle_named_pct.tput", "kv_move_dev_pct.tput",
            "unscoped_dev_pct.tput",
            "moe_dev_pct.tput", "mla_dev_pct.tput",
            "experts_touched_mean.tput", "moe_roofline_pct.tput",
            "mla_roofline_pct.tput", "decode_touched_roofline_pct"]
OWN = ["zero_pairs_pct.tput", "dense_ffn_dev_pct.tput",
       "dense_ffn_roofline_pct.tput"]
#: what later PRs appended behind them for this cell (PR 40: the join)
LATER = ["prefill_stall_pct.tput", "join_ms_per_ktok.tput",
         "join_fetch_share_pct.tput", "join_max_ms.tput",
         "joins_per_admit_mean.tput"]


def test_benchmark_json_has_the_configuration_and_the_cell():
    bench = harness.benchmark()
    conf = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(conf) == 1 and bench["configs"][4] is conf[0]
    assert conf[0]["file"] == f"perfbench/configs/{CONFIG}.json"
    assert conf[0]["reduced"] == ["num_layers", "n_routed_experts",
                                  "vocab_size"]
    assert conf[0]["source"].startswith(
        "https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/")
    row = [w for w in bench["workloads"] if w["name"] == CELL]
    assert row == [bench["workloads"][6]] == [{
        "name": CELL, "config": CONFIG, "traffic": MIX, "chips": 1,
        "why": row[0]["why"]}]
    assert all(1 <= len(x["why"]) <= 200 for x in (conf[0], row[0]))
    reported = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
                if CELL in m.get("workloads", [CELL])]
    assert reported == ACCEPTED[:1] + ["setup_s"] + ACCEPTED[2:] + OWN \
        + LATER
    # appended: the cell stood last in every list it joined (only later
    # PRs' cells stand behind it: PR 43's ``laguna-codegen``), and its own
    # three metrics together behind every per-layer metric the benchmark
    # had when it came, with only later PRs' behind them
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            behind = m["workloads"][m["workloads"].index(CELL) + 1:]
            assert behind in ([], ["laguna-codegen"], ["phi4flash-reasoning"],
                              ["laguna-codegen", "phi4flash-reasoning"])
            assert m.get("moves", "serve_tok_s") == "serve_tok_s"
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(OWN[0])
    assert names[at:at + 3] == OWN
    assert [n for n in names[at + 3:] if n in reported] == LATER
    assert names[at + 3 + len(LATER) + 1:] == [     # + join_hold_dev_pct.*
        "join_hold_dev_pct.lat", "attn_window_dev_pct.tput",
        "attn_full_dev_pct.tput", "attn_window_roofline_pct.tput",
        "attn_full_roofline_pct.tput", "cache_bytes_per_live_token.tput",
        # PR 46: the query-only attention and the memory units
        "attn_cross_dev_pct.tput", "attn_cross_roofline_pct.tput",
        "gmu_dev_pct.tput"]
    assert [(m["source"], m["layer"])
            for m in bench["per_layer"][at:at + 3]] == [
        ("program_span", "engine"), ("device_trace", "model"),
        ("device_trace", "kernels")]
    # one four-chip cell in eight: inside the quarter the contract allows
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


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
        for name in ("n_params", "latent_row_bytes", "moe_step_bytes",
                     "mla_step_bytes", "dense_ffn_step_bytes",
                     "decode_touched_bytes", "decode_step_bytes",
                     "decode_step_flops", "train_flops_per_token"):
            assert callable(getattr(cell.costs, name))
        assert callable(cell.runner.run)
        for m in cell.per_layer:
            reader = harness.load_module("layer_metrics", m["name"] + ".py")
            assert callable(reader.read), m["name"]
        for key in ("served_logit_gap_max", "served_logit_gap_mean"):
            assert cell.limit(key) > 0
        assert cell.model.build_model(cell.config).config.n_layers \
            == cell.config["num_layers"]


def test_the_mix_is_the_issues_letter_for_letter():
    """Every parameter as ISSUE 38 gives it, but for the one change it
    allows where the sets of six runs spread too widely: outputs uniform
    512-1024 in place of 256-1024. The driver's check refused the cell
    under 256-1024 as too noisy for its bound, so the change is made; the
    mix's ``output_note`` says so, with both mixes' spreads."""
    tr = harness.load_json("traffic", MIX + ".json")
    want = {
        "kind": "serve-closed", "slots": 64, "clients": 128,
        "prefill_buckets": [256, 512, 1024], "deploy_warmup": False,
        "prompt_len": {"dist": "lognormal", "median": 512, "sigma": 0.6,
                       "min": 128, "max": 1024},
        "output_len": {"dist": "uniform", "min": 512, "max": 1024},
        "max_total": 2048, "cache_pages": 64 * 32, "ramp_s": 10,
        "trace_s": 4, "check_requests": 4, "plan_requests": 1024}
    assert {k: tr[k] for k in want} == want
    assert "256-1024" in tr["output_note"] \
        and "MAKES THAT CHANGE" in tr["output_note"]
    assert "outputs uniform 512-1024" in tr["why"]
    why = next(w["why"] for w in harness.benchmark()["workloads"]
               if w["name"] == CELL)
    assert "outputs 512-1024" in why
    cfg = harness.load_json("configs", CONFIG + ".json")
    # the longest request fits a slot, and a slot's pages the pool
    assert tr["prompt_len"]["max"] + tr["output_len"]["max"] \
        == tr["max_total"] == cfg["n_positions"]
    assert tr["cache_pages"] * 64 == tr["slots"] * cfg["n_positions"]
    # one token a held expert a step, as the 32 chips see at 64 streams
    assert tr["slots"] * cfg["moe_topk"] * cfg["n_routed_experts"] \
        / cfg["router_width"] / cfg["n_routed_experts"] == 1.0


def test_the_configuration_states_its_cut():
    cfg = harness.load_json("configs", CONFIG + ".json")
    published = {
        "hidden_size": 6144, "ffn_hidden_size": 12288,
        "expert_ffn_hidden_size": 2048, "num_attention_heads": 64,
        "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "moe_topk": 12, "zero_expert_num": 256,
        "zero_expert_type": "identity", "rms_norm_eps": 1e-5,
        "rope_theta": 1e7, "max_position_embeddings": 131072}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert (cfg["num_layers"], cfg["num_layers_published"]) == (4, 28)
    assert (cfg["n_routed_experts"], cfg["n_routed_experts_published"],
            cfg["router_width"], cfg["experts_held_first"]) == (16, 512, 768,
                                                                0)
    assert (cfg["vocab_size"], cfg["vocab_size_published"]) == (16384, 131072)
    assert "32 chips share each layer" in cfg["deployment"]
    for key in ("activation", "head", "bottleneck_scales", "rope_pairs",
                "n_positions", "dtypes", "weights", "parameters"):
        assert cfg["assumed"][key], key
    assert len(cfg["departures"]) >= 3
    assert {"hidden_size", "num_layers", "router_width", "vocab_size"} \
        <= set(cfg["rehearsal"])


def test_costs_count_the_weights_shapes():
    cell = harness.Cell(CELL)
    shapes = cell.model.weight_shapes(cell.config)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)
            if len(a.shape) >= 2)
    assert n == cell.costs.n_params(cell.config)
    assert abs(n / 5.17e9 - 1) < 0.005
    # bfloat16 as held: 10.35 GB
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree.leaves(shapes))
    assert abs(held / 10.35e9 - 1) < 0.005


def test_the_cell_rehearses_through_run_py():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 38), "--seconds", "4", "--trace", "0",
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
    with no trace and spans without the new attribute. Nothing is read and
    nothing raises, so the line leaves the metric out."""
    cell = harness.Cell(CELL, rehearsal=True)
    reader = harness.load_module("layer_metrics", name + ".py")
    ctx = {"cell": cell, "device": {"kind": "cpu"}, "trace": None,
           "trace_span": (0.0, 1.0), "spans": []}
    assert reader.read(ctx) is None
