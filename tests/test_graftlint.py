"""graftlint: the static-analysis suite that encodes the repo's
hard-won invariants (ISSUE 14).

Per rule: a fixture snippet the rule MUST flag and one it must NOT
flag; plus the framework contracts — inline suppressions, baseline
freezing, one shared parse, CLI exit codes — and the tier-1 gate:
the whole package is green against the checked-in baseline, through
``run_lint`` and through the command line.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

_REPO_ROOT = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from tools.graftlint import (Finding, default_baseline_path,  # noqa: E402
                             run_lint, walk_files, write_baseline)

ALL_NEW_RULES = ("jit-purity", "typed-errors", "lock-discipline",
                 "donation-safety", "thread-hygiene")


def _lint(tmp_path, files, rules):
    """Write fixture files under tmp_path and lint them (no baseline,
    fixture-local repo root so the env-knobs repo checker stays out)."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    res = run_lint(root=str(tmp_path), rules=list(rules),
                   baseline_path=os.devnull, repo_root=str(tmp_path))
    return res.new


# ---------------------------------------------------------------------------
# jit-purity
# ---------------------------------------------------------------------------

def test_jit_purity_flags_impurity_reachable_from_named_root(tmp_path):
    bad = _lint(tmp_path, {"mod.py": """
        import os, time

        def _scale(x):
            return x * float(os.environ.get("SOME_FLAG", "1"))

        def _train_step(params, x):
            t = time.time()
            return _scale(x), t
    """}, ["jit-purity"])
    msgs = " | ".join(f.message for f in bad)
    assert any(f.rule == "jit-purity" for f in bad)
    assert "os.environ" in msgs            # reached through _scale
    assert "time.time" in msgs             # directly in the root


def test_jit_purity_flags_jit_wrapped_and_decorated_functions(tmp_path):
    bad = _lint(tmp_path, {"mod.py": """
        import functools, jax, threading

        _lock = threading.Lock()

        def step(x):
            print(x)
            return x

        step_jit = jax.jit(step, donate_argnums=(0,))

        @functools.partial(jax.jit, static_argnums=(0,))
        def other(n, x):
            with _lock:
                return x
    """}, ["jit-purity"])
    msgs = " | ".join(f.message for f in bad)
    assert "print" in msgs
    assert "lock" in msgs.lower()


def test_jit_purity_ignores_unreachable_and_jax_random(tmp_path):
    bad = _lint(tmp_path, {"mod.py": """
        import os, time, jax

        def host_helper():                    # never called from a root
            return os.environ.get("X"), time.time()

        def _train_step(params, x, rng):
            k = jax.random.fold_in(rng, 1)    # device RNG is pure
            return params, jax.random.normal(k, x.shape)
    """}, ["jit-purity"])
    assert bad == []


# ---------------------------------------------------------------------------
# typed-errors
# ---------------------------------------------------------------------------

def test_typed_errors_flags_untyped_raise_and_swallowing_except(tmp_path):
    bad = _lint(tmp_path, {"resilience/mod.py": """
        def serve(req):
            try:
                return req.run()
            except Exception:
                return None

        def refuse():
            raise RuntimeError("nope")
    """}, ["typed-errors"])
    assert len(bad) == 2
    assert {"broad" in f.message or "RuntimeError" in f.message
            for f in bad} == {True}


def test_typed_errors_accepts_resolution_and_shielded_handlers(tmp_path):
    bad = _lint(tmp_path, {"serving/mod.py": """
        class P:
            def a(self, req):
                try:
                    return req.run()
                except Exception as e:
                    self._fail_request(req, e)   # resolves via claim()

            def b(self, req):
                try:
                    return req.run()
                except ShedError:
                    raise                        # taxonomy re-raised
                except Exception:
                    return None                  # shielded above

            def c(self, req):
                try:
                    return req.run()
                except Exception:
                    raise                        # re-raise is fine

        try:
            import fancy_dep                     # module-level guard
        except Exception:
            fancy_dep = None
    """}, ["typed-errors"])
    assert bad == []


def test_typed_errors_broad_handler_cannot_shield_itself(tmp_path):
    """`except (ShedError, Exception):` names the taxonomy AND swallows
    it — only a PRECEDING taxonomy clause shields a broad handler."""
    bad = _lint(tmp_path, {"parallel/mod.py": """
        def f(req):
            try:
                return req.run()
            except (ShedError, Exception):
                return None
    """}, ["typed-errors"])
    assert len(bad) == 1 and "broad" in bad[0].message


def test_typed_errors_only_applies_to_the_three_trees(tmp_path):
    bad = _lint(tmp_path, {"observability/mod.py": """
        def f():
            raise RuntimeError("telemetry tree is out of scope")
    """}, ["typed-errors"])
    assert bad == []


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------

def test_lock_discipline_flags_unlocked_deque_iteration(tmp_path):
    bad = _lint(tmp_path, {"mod.py": """
        import threading
        from collections import deque

        class Ring:
            def __init__(self):
                self._lock = threading.Lock()
                self._ring = deque(maxlen=8)

            def snapshot(self):
                return [x for x in self._ring]      # the PR-6 race
    """}, ["lock-discipline"])
    assert len(bad) == 1 and "deque" in bad[0].message


def test_lock_discipline_flags_blocking_calls_under_lock(tmp_path):
    bad = _lint(tmp_path, {"mod.py": """
        import threading, queue

        class Q:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue()

            def pop(self):
                with self._lock:
                    return self._q.get()            # untimed block

            def save(self, rec):
                with self._lock:
                    with open("/tmp/x", "a") as f:  # I/O under lock
                        f.write(rec)

            def place(self, x):
                with self._lock:
                    return device_put(x)            # device sync
    """}, ["lock-discipline"])
    msgs = " | ".join(f.message for f in bad)
    assert len(bad) == 3
    assert ".get()" in msgs and "open" in msgs and "device_put" in msgs


def test_lock_discipline_accepts_locked_iteration_and_timed_get(tmp_path):
    bad = _lint(tmp_path, {"mod.py": """
        import threading, queue
        from collections import deque

        class Ring:
            def __init__(self):
                self._lock = threading.Lock()
                self._ring = deque(maxlen=8)
                self._q = queue.Queue()

            def snapshot(self):
                with self._lock:
                    return list(self._ring)

            def pop(self):
                return self._q.get(timeout=1.0)     # not under a lock

            def pop2(self):
                with self._lock:
                    return self._q.get_nowait()
    """}, ["lock-discipline"])
    assert bad == []


def test_lock_discipline_dict_needs_under_lock_evidence(tmp_path):
    # iterated under the lock in one method and bare in another: flag
    bad = _lint(tmp_path, {"mod.py": """
        import threading

        class Reg:
            def __init__(self):
                self._lock = threading.Lock()
                self._entries = {}

            def locked_view(self):
                with self._lock:
                    return {k: v for k, v in self._entries.items()}

            def racy_view(self):
                return [k for k in self._entries]
    """}, ["lock-discipline"])
    assert len(bad) == 1 and "dict" in bad[0].message
    # a dict never iterated under a lock carries no shared-use evidence
    ok = _lint(tmp_path / "b", {"mod.py": """
        import threading

        class Reg:
            def __init__(self):
                self._lock = threading.Lock()
                self._entries = {}

            def view(self):
                return list(self._entries)
    """}, ["lock-discipline"])
    assert ok == []


def test_lock_discipline_ignores_function_local_containers(tmp_path):
    """A function-LOCAL dict/deque is not module state: a module-level
    lock elsewhere must not turn local iteration into a finding."""
    bad = _lint(tmp_path, {"mod.py": """
        import threading
        from collections import deque

        _lock = threading.Lock()          # module lock exists

        def summarize(records):
            cfg = {}
            with _lock:
                ks = [k for k in cfg.items()]
            return ks

        def other():
            cfg = {}
            return [k for k in cfg]       # same NAME, different local

        def third():
            local = deque()
            return list(local)            # local deque, no lock needed
    """}, ["lock-discipline"])
    assert bad == []


def test_lock_discipline_knows_condition_attrs_are_locks(tmp_path):
    """`with self._cv:` (a Condition assigned in __init__) holds the
    lock — iteration under it passes, blocking calls under it flag."""
    bad = _lint(tmp_path, {"mod.py": """
        import threading
        from collections import deque

        class Writer:
            def __init__(self):
                self._cv = threading.Condition()
                self._pending = deque()

            def ok_snapshot(self):
                with self._cv:
                    return list(self._pending)       # correctly locked

            def blocks_everyone(self, q):
                with self._cv:
                    return q.get()                   # untimed, held
    """}, ["lock-discipline"])
    assert len(bad) == 1 and ".get()" in bad[0].message


def test_lock_discipline_module_level_ring(tmp_path):
    bad = _lint(tmp_path, {"mod.py": """
        import threading
        from collections import deque

        _events = deque(maxlen=256)
        _events_lock = threading.Lock()

        def snapshot():
            return list(_events)

        def snapshot_ok():
            with _events_lock:
                return list(_events)
    """}, ["lock-discipline"])
    assert len(bad) == 1 and "deque" in bad[0].message


# ---------------------------------------------------------------------------
# donation-safety
# ---------------------------------------------------------------------------

def test_donation_flags_read_after_donating_call(tmp_path):
    bad = _lint(tmp_path, {"mod.py": """
        import jax

        def run(f, buf, x):
            g = jax.jit(f, donate_argnums=(0,))
            y = g(buf, x)
            return buf.sum() + y          # buf's buffer is gone
    """}, ["donation-safety"])
    assert len(bad) == 1
    assert "buf" in bad[0].message and "donated" in bad[0].message


def test_donation_flags_attr_bound_jit_across_methods(tmp_path):
    bad = _lint(tmp_path, {"mod.py": """
        import jax

        class Engine:
            def __init__(self, f):
                self._decode = jax.jit(f, donate_argnums=(1,))

            def step(self, params, cache, tok):
                out = self._decode(params, cache, tok)
                return out, cache.shape   # cache was donated
    """}, ["donation-safety"])
    assert len(bad) == 1 and "cache" in bad[0].message


def test_donation_accepts_rebinding_idiom(tmp_path):
    bad = _lint(tmp_path, {"mod.py": """
        import functools, jax

        class Engine:
            def __init__(self, f):
                self._decode = jax.jit(f, donate_argnums=(1,))

            def generate(self, params, cache, n):
                for _ in range(n):
                    cache, tok = self._decode(params, cache)
                return cache

            @functools.partial(jax.jit, static_argnums=(0,),
                               donate_argnums=(1,))
            def _train_step(self, params, x):
                return params, x

            def fit(self, params, x):
                params, _ = self._train_step(params, x)
                return params
    """}, ["donation-safety"])
    assert bad == []


def test_donation_decorated_method_shifts_positions(tmp_path):
    bad = _lint(tmp_path, {"mod.py": """
        import functools, jax

        class Net:
            @functools.partial(jax.jit, static_argnums=(0,),
                               donate_argnums=(1,))
            def _train_step(self, params, x):
                return params, x

            def fit(self, params, x):
                new_params, _ = self._train_step(params, x)
                return params          # old params read after donation
    """}, ["donation-safety"])
    assert len(bad) == 1 and "params" in bad[0].message


# ---------------------------------------------------------------------------
# thread-hygiene
# ---------------------------------------------------------------------------

def test_thread_hygiene_flags_orphan_thread(tmp_path):
    bad = _lint(tmp_path, {"mod.py": """
        import threading

        def start(worker):
            t = threading.Thread(target=worker)
            t.start()
            return t
    """}, ["thread-hygiene"])
    assert len(bad) == 1 and "daemon" in bad[0].message


def test_thread_hygiene_accepts_daemon_joined_and_pools(tmp_path):
    bad = _lint(tmp_path, {"mod.py": """
        import threading

        class Svc:
            def start(self):
                self._t = threading.Thread(target=self._run)
                self._t.start()

            def shutdown(self):
                self._t.join(timeout=5.0)

        def fire_and_forget(fn):
            threading.Thread(target=fn, daemon=True).start()

        def pool(fn, n):
            ts = [threading.Thread(target=fn) for _ in range(n)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
    """}, ["thread-hygiene"])
    assert bad == []


# ---------------------------------------------------------------------------
# migrated rules: metric-names + env-knobs run inside graftlint
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# store-discipline
# ---------------------------------------------------------------------------

def test_store_discipline_flags_raw_writes_in_serving(tmp_path):
    bad = _lint(tmp_path, {"serving/sneaky.py": """
        def hijack(store, doc):
            store._write(doc)                       # bypasses everything
            store.try_replace(doc, doc.get("rev"))  # bypasses the fence
    """}, ["store-discipline"])
    assert len(bad) == 2
    assert all(f.rule == "store-discipline" for f in bad)
    assert "leader fence" in bad[0].message


def test_store_discipline_exempts_owner_and_outside_serving(tmp_path):
    ok = _lint(tmp_path, {
        # shared_state.py OWNS both spellings
        "serving/shared_state.py": """
            def update(store, doc):
                store._write(doc)
                store.try_replace(doc, 0)
        """,
        # sanctioned helpers are fine anywhere in serving/
        "serving/fine.py": """
            def beat(state, store):
                store.update(lambda d: None)
                state.sync()
        """,
        # outside serving/ is out of scope (drills/tests poke internals)
        "tools_like.py": """
            def drill(store, doc):
                store.try_replace(doc, 0)
        """,
    }, ["store-discipline"])
    assert ok == []


def test_metric_names_runs_as_graftlint_rule(tmp_path):
    bad = _lint(tmp_path, {"mod.py": """
        def install(reg):
            reg.counter("dl4j_requests", "d")       # missing _total
            reg.histogram("dl4j_wait", "d")         # missing unit
            reg.gauge("dl4j_depth", "queue depth")  # fine
    """}, ["metric-names"])
    assert len(bad) == 2
    assert all(f.rule == "metric-names" for f in bad)


def test_span_names_flags_interpolated_and_bad_case(tmp_path):
    bad = _lint(tmp_path, {"mod.py": """
        from deeplearning4j_tpu.observability import record_span, span

        def handle(i, name, t0):
            with span(f"request_{i}"):            # f-string: unbounded
                pass
            with span("BadName"):                 # not snake_case
                pass
            record_span("wait-" + str(i), t0)     # concatenation
            record_span(name, t0)                 # variable
    """}, ["span-names"])
    assert len(bad) == 4
    assert all(f.rule == "span-names" for f in bad)
    msgs = " | ".join(f.message for f in bad)
    assert "f-string" in msgs
    assert "snake_case" in msgs


def test_span_names_accepts_literals_and_unrelated_calls(tmp_path):
    ok = _lint(tmp_path, {"mod.py": """
        import re
        from deeplearning4j_tpu.observability import record_span, span
        from deeplearning4j_tpu.observability import span as _span

        def handle(i, m: "re.Match", t0):
            with span("http_request", route="generate", shard=i):
                pass
            with _span("checkpoint.save", path="x"):  # dotted ok
                pass
            record_span("queue_wait", t0, attrs_id=i)
            a, b = m.span(1)       # Attribute call: out of scope
            span()                 # zero-arg: not a name site
    """}, ["span-names"])
    assert ok == []


def test_detector_rule_names_flags_interpolated_and_bad_namespace(tmp_path):
    bad = _lint(tmp_path, {"mod.py": """
        from deeplearning4j_tpu.observability.watchtower import (
            BurnRateDetector, ChangePointDetector, ThresholdDetector)

        def build(name, fn):
            return [
                BurnRateDetector(f"watch_{name}"),          # f-string
                ChangePointDetector(name, fn),              # variable
                ThresholdDetector(rule="watch-bad", value_fn=fn,
                                  firing_above=1.0),        # bad charset
                BurnRateDetector("error_burn"),             # no namespace
            ]
    """}, ["detector-rule-names"])
    assert len(bad) == 4
    assert all(f.rule == "detector-rule-names" for f in bad)
    msgs = " | ".join(f.message for f in bad)
    assert "f-string" in msgs
    assert "(watch|fleet)_" in msgs


def test_detector_rule_names_accepts_literals_and_unrelated_calls(tmp_path):
    ok = _lint(tmp_path, {"mod.py": """
        from deeplearning4j_tpu.observability import watchtower as wt
        from deeplearning4j_tpu.observability.watchtower import (
            BurnRateDetector, Detector, ThresholdDetector)

        def build(fn, totals):
            return [
                BurnRateDetector("watch_http_error_burn"),
                wt.ChangePointDetector("watch_p99_shift", fn),
                ThresholdDetector(rule="fleet_workers_missing",
                                  value_fn=fn, firing_above=0.5),
                BurnRateDetector("fleet_error_burn", totals_fn=totals),
            ]

        class _Double(Detector):
            # subclassing the base is the extension point — out of scope
            def __init__(self, rule):
                super().__init__(rule)
    """}, ["detector-rule-names"])
    assert ok == []


def test_back_compat_shims_serve_the_original_api():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_metric_names_shim",
        os.path.join(_REPO_ROOT, "tools", "check_metric_names.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.check_source('r.counter("bad_name", "d")') != []
    assert mod.check_source('r.counter("dl4j_ok_total", "d")') == []

    spec = importlib.util.spec_from_file_location(
        "check_env_knobs_shim",
        os.path.join(_REPO_ROOT, "tools", "check_env_knobs.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.check_repo(_REPO_ROOT) == []


def test_shared_parse_is_reused_across_checkers(tmp_path):
    """The walker parses each file once; every checker sees the same
    tree object (the pre-graftlint lints each parsed independently)."""
    p = tmp_path / "mod.py"
    p.write_text("x = 1\n")
    [ctx] = walk_files(str(tmp_path))
    t1 = ctx.tree
    t2 = ctx.tree
    assert t1 is t2 and t1 is not None


# ---------------------------------------------------------------------------
# framework: suppressions + baseline
# ---------------------------------------------------------------------------

def test_inline_suppression_same_line_and_comment_block(tmp_path):
    files = {"resilience/mod.py": """
        def a():
            raise RuntimeError("x")  # graftlint: disable=typed-errors — demo

        def b():
            # graftlint: disable=typed-errors — justified across a
            # multi-line comment block directly above the finding
            raise RuntimeError("y")

        def c():
            # graftlint: disable=lock-discipline — WRONG rule id
            raise RuntimeError("z")
    """}
    bad = _lint(tmp_path, files, ["typed-errors"])
    assert len(bad) == 1                    # only c() survives
    assert "raise RuntimeError" in bad[0].message


def test_baseline_freezes_old_violations_and_fails_new_ones(tmp_path):
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "mod.py").write_text(textwrap.dedent("""
        import threading
        from collections import deque

        class Ring:
            def __init__(self):
                self._lock = threading.Lock()
                self._ring = deque()

            def old_racy(self):
                return list(self._ring)
    """))
    baseline = tmp_path / "baseline.json"
    n = write_baseline(root=str(root), baseline_path=str(baseline),
                       rules=["lock-discipline"], repo_root=str(tmp_path))
    assert n == 1
    doc = json.loads(baseline.read_text())
    assert doc["entries"][0]["rule"] == "lock-discipline"

    res = run_lint(root=str(root), rules=["lock-discipline"],
                   baseline_path=str(baseline), repo_root=str(tmp_path))
    assert res.new == [] and len(res.baselined) == 1

    # line drift must not resurrect the frozen finding...
    (root / "mod.py").write_text(
        "# a new leading comment shifts every line\n"
        + (root / "mod.py").read_text())
    res = run_lint(root=str(root), rules=["lock-discipline"],
                   baseline_path=str(baseline), repo_root=str(tmp_path))
    assert res.new == [] and len(res.baselined) == 1

    # ...but a NEW violation of the same rule fails
    (root / "mod.py").write_text(
        (root / "mod.py").read_text() + textwrap.dedent("""
            def new_racy(self):
                return tuple(self._ring)
        """).replace("\n", "\n    ").rstrip() + "\n")
    res = run_lint(root=str(root), rules=["lock-discipline"],
                   baseline_path=str(baseline), repo_root=str(tmp_path))
    assert len(res.new) == 1 and "tuple" not in res.new[0].message


def test_filtered_baseline_update_preserves_other_rules(tmp_path):
    """`--rule X --baseline-update` replaces only X's frozen entries —
    every other rule's baseline survives verbatim."""
    root = tmp_path / "pkg"
    (root / "resilience").mkdir(parents=True)
    (root / "resilience" / "mod.py").write_text(textwrap.dedent("""
        import threading

        def refuse():
            raise RuntimeError("x")

        def orphan(fn):
            threading.Thread(target=fn).start()
    """))
    baseline = tmp_path / "baseline.json"
    # freeze BOTH rules, then re-freeze only thread-hygiene
    write_baseline(root=str(root), baseline_path=str(baseline),
                   rules=["typed-errors", "thread-hygiene"],
                   repo_root=str(tmp_path))
    write_baseline(root=str(root), baseline_path=str(baseline),
                   rules=["thread-hygiene"], repo_root=str(tmp_path))
    rules_frozen = {e["rule"]
                    for e in json.loads(baseline.read_text())["entries"]}
    assert rules_frozen == {"typed-errors", "thread-hygiene"}
    res = run_lint(root=str(root), baseline_path=str(baseline),
                   repo_root=str(tmp_path))
    assert res.new == [] and len(res.baselined) == 2


def test_parse_errors_respect_the_rule_filter(tmp_path):
    (tmp_path / "bad.py").write_text("def broken(:\n")
    # a single-rule run must not fail on a file its rule never inspects
    res = run_lint(root=str(tmp_path), rules=["metric-names"],
                   baseline_path=os.devnull, repo_root=str(tmp_path))
    assert res.new == []
    # the unfiltered run reports the unparseable file
    res = run_lint(root=str(tmp_path), baseline_path=os.devnull,
                   repo_root=str(tmp_path))
    assert [f.rule for f in res.new] == ["parse"]


# ---------------------------------------------------------------------------
# CLI + tier-1 gates
# ---------------------------------------------------------------------------

def test_cli_exits_nonzero_on_seeded_violations_of_every_rule(tmp_path):
    (tmp_path / "resilience").mkdir()
    (tmp_path / "resilience" / "mod.py").write_text(textwrap.dedent("""
        import jax, threading, time
        from collections import deque

        def refuse():
            raise RuntimeError("untyped")                 # typed-errors

        def _train_step(x):
            return x * time.time()                        # jit-purity

        def donate(f, buf):
            g = jax.jit(f, donate_argnums=(0,))
            y = g(buf)
            return buf + y                                # donation-safety

        def orphan(fn):
            threading.Thread(target=fn).start()           # thread-hygiene

        class Ring:
            def __init__(self):
                self._lock = threading.Lock()
                self._ring = deque()

            def racy(self):
                return list(self._ring)                   # lock-discipline
    """))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--root", str(tmp_path),
         "--no-baseline"],
        capture_output=True, text=True, cwd=_REPO_ROOT)
    assert proc.returncode >= 5, proc.stdout + proc.stderr
    for rule in ALL_NEW_RULES:
        assert f"[{rule}]" in proc.stdout, (rule, proc.stdout)


def test_package_is_green_against_the_baseline():
    """Tier-1 gate: the whole package passes graftlint (fixes landed,
    deliberate exemptions carry inline justifications, baseline empty
    or justified)."""
    res = run_lint()
    assert res.new == [], "\n".join(str(f) for f in res.new)
    # the checked-in baseline stays empty: exemptions are inline
    doc = json.loads(open(default_baseline_path()).read())
    assert doc["entries"] == []


def test_cli_exits_zero_on_the_repo(capsys):
    """The command a shell or CI step runs, ``python -m tools.graftlint``:
    exit code 0 on the package as it stands."""
    from tools.graftlint.cli import main
    assert main([]) == 0
    assert "graftlint: OK" in capsys.readouterr().out
