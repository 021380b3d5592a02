"""Training-UI monitoring: live SSE streaming + two-session compare.

The analog of the reference's UIServer example (ref: org.deeplearning4j.ui
VertxUIServer + StatsListener usage in dl4j-examples): attach a
StatsListener to a network, open the browser at the printed address, and
watch the score chart update live over Server-Sent Events while training
runs. Trains TWO sessions with different learning rates and prints the
compare-view URL that renders them side by side.

Run: python examples/ui_monitoring.py [--steps N] [--port P]
"""
import argparse

import numpy as np  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--port", type=int, default=9007)
    ap.add_argument("--keep-serving", action="store_true",
                    help="block at the end so the page stays browsable")
    args = ap.parse_args()

    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optim.updaters import Adam
    from deeplearning4j_tpu.ui import InMemoryStatsStorage, StatsListener, UIServer

    server = UIServer.get_instance(port=args.port)
    storage = InMemoryStatsStorage()
    server.attach(storage)
    server.start()
    print(f"UI at {server.get_address()}  (score chart updates over SSE "
          f"at /train/stream)")

    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 8)).astype(np.float32)
    w_true = rng.normal(size=(8, 3)).astype(np.float32)
    logits = x @ w_true
    y = np.eye(3, dtype=np.float32)[logits.argmax(1)]

    sids = []
    for lr in (1e-2, 1e-3):
        conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(lr))
                .weight_init("xavier").list()
                .layer(L.DenseLayer(n_in=8, n_out=32, activation="relu"))
                .layer(L.OutputLayer(n_in=32, n_out=3, activation="softmax",
                                     loss_function="negativeloglikelihood"))
                .build())
        net = MultiLayerNetwork(conf).init()
        sid = f"adam_lr{lr:g}"
        net.setListeners(StatsListener(storage, session_id=sid))
        for _ in range(args.steps):
            net.fit(x, y)
        sids.append(sid)
        print(f"session {sid}: final score {float(net.score()):.4f}")

    print(f"compare the runs: {server.get_address()}/train/compare"
          f"?sids={','.join(sids)}")

    # ---- observability: scrape /metrics alongside the stats UI ----------
    # the same server exposes the process-wide registry in Prometheus text
    # format (counters/gauges/histograms every layer publishes into) plus a
    # JSON health probe — point a real Prometheus at this URL in production
    import urllib.error
    import urllib.request
    metrics_text = urllib.request.urlopen(
        server.get_address() + "/metrics", timeout=5).read().decode()
    interesting = [l for l in metrics_text.splitlines()
                   if l.startswith(("dl4j_training_step_seconds_count",
                                    "dl4j_training_examples_total",
                                    "dl4j_training_score",
                                    "dl4j_slow_steps_total",
                                    "dl4j_data_batches_total"))]
    print(f"\nscraped {server.get_address()}/metrics "
          f"({len(metrics_text.splitlines())} lines); highlights:")
    for line in interesting:
        print("  " + line)
    # ---- exemplar → trace lookup (causal observability) -----------------
    # serve a few requests so the latency histogram gets bucket exemplars:
    # each observation carries the trace_id of the request that produced
    # it, linking a /metrics tail bucket straight to its trace
    import json as _json

    from deeplearning4j_tpu.parallel.inference import (InferenceMode,
                                                       ParallelInference)
    pi = (ParallelInference.Builder(net)
          .inference_mode(InferenceMode.BATCHED).batch_limit(8).build())
    try:
        for i in range(8):
            pi.output(x[i:i + 2])
    finally:
        pi.shutdown()
    # exemplars render only in the OpenMetrics flavor (real Prometheus
    # negotiates this Accept when exemplar scraping is enabled; the plain
    # 0.0.4 payload stays strictly parseable)
    om_req = urllib.request.Request(
        server.get_address() + "/metrics",
        headers={"Accept": "application/openmetrics-text"})
    metrics_text = urllib.request.urlopen(om_req, timeout=5).read().decode()
    ex_line = next(
        (l for l in metrics_text.splitlines()
         if l.startswith("dl4j_inference_latency_seconds_bucket")
         and "# {" in l), None)
    if ex_line:
        trace_id = ex_line.split('trace_id="')[1].split('"')[0]
        print(f"\nexemplar bucket: {ex_line}")
        trace = _json.loads(urllib.request.urlopen(
            server.get_address() + "/train/trace", timeout=5).read())
        phases = sorted(
            (e for e in trace if e["ph"] == "X"
             and e.get("args", {}).get("trace_id") == trace_id),
            key=lambda e: e["ts"])
        print(f"trace {trace_id} — the request behind that bucket:")
        for e in phases:
            print(f"  {e['name']:<20} {e['dur'] / 1e3:8.3f} ms "
                  f"(tid {e['tid']})")

    # ---- compile watch: /debug/compiles ---------------------------------
    # every XLA trace of the jitted entry points, with the arg signature
    # that triggered it: the training fit compiled the train step once,
    # and each ParallelInference shape bucket above compiled one output
    # executable whose event carries cause=bucket_miss. When a step
    # suddenly runs 40x median, this ring answers "did we just recompile,
    # and what shape caused it" before you ever open a profile
    compiles = _json.loads(urllib.request.urlopen(
        server.get_address() + "/debug/compiles", timeout=5).read())
    print(f"\n/debug/compiles: {compiles['total_traces']} traces, "
          f"storm status {compiles['storm']['status']}")
    for ev in compiles["events"]:
        cause = ev.get("cause")
        print(f"  #{ev['seq']} {ev['fn']}({ev['signature']})"
              + (f" [{cause['cause']}]" if cause else "")
              + (f" compiled in {ev['compile_seconds']:.3f}s"
                 if ev.get("compile_seconds") is not None else ""))

    # ---- performance observatory: /debug/perf ---------------------------
    # per-entry-point FLOPs/bytes from the XLA cost model (accounted once
    # per compile), live MFU against the peak table in force, and the
    # roofline verdict — "is this step fast?" without running a bench.
    # The train step above and each serving bucket executable have rows
    perf = _json.loads(urllib.request.urlopen(
        server.get_address() + "/debug/perf", timeout=5).read())
    print(f"\n/debug/perf: platform={perf['platform']}, "
          f"peak={perf['peak_flops']:.3g} FLOP/s, "
          f"ridge={perf['ridge_intensity']:.2f} FLOPs/byte")
    for fn, rec in perf["fns"].items():
        if rec.get("flops") is None:
            continue
        mfu = rec.get("mfu")
        # intensity/verdict are None when the backend reports no bytes
        intensity = rec.get("arithmetic_intensity")
        print(f"  {fn:<40} {rec['flops']:.3g} FLOPs "
              + (f"intensity={intensity:.2f} " if intensity is not None
                 else "")
              + f"[{rec.get('roofline_verdict') or 'no-bytes'}]"
              + (f" mfu={mfu:.4f}" if mfu is not None else ""))

    # ---- on-demand device profiling: /debug/profile ---------------------
    # drives the jax profiler against THIS running process (no restart)
    # until N more work units complete, and serves the parsed top-K
    # per-op device-time table; captures are retained under the
    # postmortem retention cap and refused when DL4J_TPU_PROFILE=0
    import threading as _threading
    prof_net = net

    def _background_steps():
        for _ in range(10):
            prof_net.fit(x, y)

    t = _threading.Thread(target=_background_steps, daemon=True)
    t.start()
    try:
        cap = _json.loads(urllib.request.urlopen(
            server.get_address() + "/debug/profile?steps=3&timeout_s=30",
            timeout=60).read())
        print(f"\n/debug/profile capture {cap['id']}: "
              f"{cap['steps_seen']} work units in "
              f"{cap['duration_seconds']:.2f}s "
              f"(source={cap.get('source', '?')})")
        for row in cap.get("top_ops", [])[:5]:
            print(f"  {row['op']:<48} {row['total_seconds'] * 1e3:9.3f} ms "
                  f"x{row['count']}")
    except urllib.error.HTTPError as e:     # 403 kill switch / 409 busy
        print(f"\n/debug/profile refused: {e.code} {e.read().decode()}")
    t.join()

    # ---- resilience: /debug/resilience ----------------------------------
    # fault-injection counts (chaos runs are auditable), circuit-breaker
    # states, the default serving deadline, and the recent event ring
    # (retries, sheds, breaker transitions, restores, quarantines)
    res = _json.loads(urllib.request.urlopen(
        server.get_address() + "/debug/resilience", timeout=5).read())
    circuits = [f"{c['op']}={c['state']}" for c in res["circuits"]]
    print(f"\n/debug/resilience: enabled={res['enabled']}, "
          f"injected={res['faults']['injected']}, circuits={circuits}, "
          f"{len(res['events'])} events")

    # ---- multi-tenant QoS: /debug/tenants -------------------------------
    # tenant policies (weights, priority tiers, quotas), live token-
    # bucket levels, and per-tenant request/token/shed/cost counters —
    # which tenant is flooding and who is being shed
    tn = _json.loads(urllib.request.urlopen(
        server.get_address() + "/debug/tenants", timeout=5).read())
    rows = [f"{name}: req={t['requests']} shed={t['shed']}"
            for name, t in sorted(tn["tenants"].items())]
    print(f"\n/debug/tenants: enabled={tn['enabled']}, "
          f"top_n={tn['top_n']}, {rows or ['no tenants yet']}")

    # ---- elastic training: /debug/elastic -------------------------------
    # device-capacity view (host losses shrink it, healthy steps on the
    # degraded mesh restore it), mesh reshape history, and the sharded
    # manifest checkpoint stores with their newest complete step
    el = _json.loads(urllib.request.urlopen(
        server.get_address() + "/debug/elastic", timeout=5).read())
    cap = el["capacity"]
    print(f"\n/debug/elastic: enabled={el['enabled']}, "
          f"capacity={cap['available']}/{cap['total_devices']}, "
          f"reshapes={el['reshapes']}, "
          f"{len(el['checkpointers'])} manifest store(s)")

    # ---- SLO-driven health + alerts -------------------------------------
    # /health grades measured SLOs (p99 latency, error rate, queue depth,
    # prefetch overlap, retrace storms, numerics divergence) and returns
    # HTTP 503 when a rule fails; /alerts lists active violations;
    # /debug/dump writes a postmortem bundle
    try:
        health = _json.loads(urllib.request.urlopen(
            server.get_address() + "/health", timeout=5).read())
    except urllib.error.HTTPError as e:      # 503 when an SLO rule fails
        health = _json.loads(e.read())
    print(f"\nhealth: {health['status']}"
          f" (degraded={health['degraded_rules']},"
          f" failing={health['failing_rules']})")
    for rule in health["rules"]:
        print(f"  {rule['rule']:<32} {rule['status']}")

    # ---- fleet observability plane: /metrics/fleet + /health/fleet ------
    # one worker registered in a shared store, a traced request through
    # its front door (the caller's X-Dl4j-Trace-Id comes back on the
    # response — the same id the worker's spans carry), then the
    # federated scrape: every live worker's series merged under a
    # worker="..." label, and the fleet health rollup graded over them
    import os as _os
    import tempfile as _tempfile

    from deeplearning4j_tpu.serving import ModelRegistry, ServingRouter
    from deeplearning4j_tpu.serving.frontdoor import FrontDoor
    from deeplearning4j_tpu.serving.shared_state import (SharedServingState,
                                                         SharedStore)

    fleet_reg = ModelRegistry()
    fleet_reg.deploy("v1", net, sample_input=x[:1], batch_limit=8,
                     max_wait_ms=1.0)
    fleet_store = SharedStore(_tempfile.mkdtemp(prefix="dl4j-ui-fleet-"))
    shared = SharedServingState(fleet_store, "fw0")
    shared.ensure_lane("scoring", "v1")
    door = FrontDoor(ServingRouter(fleet_reg, "v1"), None, shared=shared,
                     port=0).start()
    shared.register(_os.getpid(), door.port)
    try:
        # let the sync loop take the leader lease (a leaderless fleet
        # grades fleet_leader_staleness degraded — correctly)
        import time as _time
        for _ in range(40):
            if (fleet_store.read().get("leader") or {}).get("worker"):
                break
            _time.sleep(0.1)
        # keep every trace for the walk below (the default 1% head coin
        # would usually discard this single boring request)
        _os.environ["DL4J_TPU_TRACE_SAMPLE"] = "1.0"
        req = urllib.request.Request(
            f"http://127.0.0.1:{door.port}/v1/classify",
            data=_json.dumps({"inputs": x[:1].tolist()}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Dl4j-Trace-Id": "cafe0000deadbeef"})
        with urllib.request.urlopen(req, timeout=10) as r:
            r.read()
            echoed = r.headers.get("X-Dl4j-Trace-Id")
        print(f"\ntraced request: sent trace id cafe0000deadbeef, "
              f"response echoed {echoed}")
        fleet_text = urllib.request.urlopen(
            f"http://127.0.0.1:{door.port}/metrics/fleet",
            timeout=10).read().decode()
        highlights = [l for l in fleet_text.splitlines()
                      if 'worker="' in l
                      and l.startswith(("dl4j_http_requests_total",
                                        "dl4j_fleet_scrape"))][:6]
        print(f"/metrics/fleet ({len(fleet_text.splitlines())} lines, "
              f"every series labeled by worker); highlights:")
        for line in highlights:
            print("  " + line)
        try:
            fleet_health = _json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{door.port}/health/fleet",
                timeout=10).read())
        except urllib.error.HTTPError as e:   # 503 when the fleet FAILS
            fleet_health = _json.loads(e.read())
        print(f"/health/fleet: {fleet_health['status']} "
              f"(workers scraped: {fleet_health['workers_scraped']})")
        for rule in fleet_health["rules"]:
            by = rule.get("worker")
            print(f"  {rule['rule']:<32} {rule['status']}"
                  + (f" (worst: {by})" if by else ""))

        # ---- trace intelligence: /debug/trace ---------------------------
        # the traced request above completed; the trace store ran its
        # keep/discard decision on it (errors and latency-tail outliers
        # are always kept; boring traffic rides the DL4J_TPU_TRACE_SAMPLE
        # coin — forced to 1.0 above so this walk is deterministic).
        # /debug/trace/recent lists retained traces with why-kept
        # reasons; /debug/trace/<id> assembles the id across every live
        # worker into one latency waterfall
        recent = _json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{door.port}/debug/trace/recent",
            timeout=10).read())
        print(f"/debug/trace/recent: {len(recent['traces'])} retained")
        for t in recent["traces"][:4]:
            print(f"  {t['trace_id']} reason={t['reason']} "
                  f"root={t['root']} {t['dur_us'] / 1e3:.2f} ms")
        assembled = None
        for _ in range(40):          # span close lands after the reply
            try:
                assembled = _json.loads(urllib.request.urlopen(
                    f"http://127.0.0.1:{door.port}"
                    "/debug/trace/cafe0000deadbeef", timeout=10).read())
                break
            except urllib.error.HTTPError:
                _time.sleep(0.1)
        if assembled:
            print(f"waterfall for cafe0000deadbeef "
                  f"(workers={assembled['workers']}, "
                  f"reasons={assembled['reasons']}, "
                  f"{assembled['duration_us'] / 1e3:.2f} ms total):")
            for row in assembled["waterfall"]:
                bar = "  " * row["depth"]
                print(f"  {bar}{row['name']:<24} "
                      f"+{row['offset_us'] / 1e3:7.3f} ms "
                      f"{row['dur_us'] / 1e3:8.3f} ms "
                      f"[{row['worker']}]"
                      + (" ERROR" if row["error"] else ""))
            # ?format=chrome exports the same assembly as Perfetto-
            # loadable events (per-worker pids, cross-process flow
            # arrows); unknown ids are a 404, never a 500
        else:
            print("trace cafe0000deadbeef not retained (store off?)")

        # ---- watchtower: fire an alert and watch the loop close ---------
        # the detectors upstairs watch scraped series; here we make one
        # page deterministically: scale the burn-rate windows down (env
        # knobs are read live), then send a burst of unmeetable-deadline
        # requests — every one sheds as an in-span 504, the error budget
        # burns in BOTH windows, and watch_http_error_burn walks
        # pending -> firing. Polling /debug/alerts drives the beats.
        _os.environ["DL4J_TPU_WATCHTOWER_FAST_S"] = "1.0"
        _os.environ["DL4J_TPU_WATCHTOWER_SLOW_S"] = "2.0"
        _os.environ["DL4J_TPU_WATCHTOWER_HOLD_S"] = "0.0"
        _os.environ["DL4J_TPU_WATCHTOWER_INTERVAL_S"] = "0.1"
        _os.environ["DL4J_TPU_TIMESERIES_INTERVAL_S"] = "0.1"
        firing = []
        for k in range(80):
            bad = urllib.request.Request(
                f"http://127.0.0.1:{door.port}/v1/classify",
                data=_json.dumps({"inputs": x[:1].tolist(),
                                  "deadline_ms": 0.001}).encode(),
                headers={"Content-Type": "application/json"})
            try:
                urllib.request.urlopen(bad, timeout=10).read()
            except urllib.error.HTTPError as e:     # the 504 we want
                e.read()
            alerts = _json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{door.port}/debug/alerts",
                timeout=10).read())
            firing = (alerts.get("watchtower") or {}).get("firing") or []
            if any(a["rule"] == "watch_http_error_burn" for a in firing):
                break
            _time.sleep(0.1)
        print("/debug/alerts after the 504 burst:")
        for a in firing:
            print(f"  FIRING {a['rule']} [{a['severity']}] — "
                  f"{a.get('description', '')}")
        if any(a["rule"] == "watch_http_error_burn" and
               a["severity"] == "page" for a in firing):
            # a PAGE going firing already closed the detect->capture
            # loop: offending retained traces pinned, the incident
            # window open, a flight-recorder bundle on disk — the
            # postmortem existed before we looked
            from deeplearning4j_tpu.observability import (
                global_trace_store, global_watchtower)
            snap = global_watchtower().snapshot()
            print(f"  loop closed: incident="
                  f"{snap['last_incident_reason']} "
                  f"pinned={len(global_trace_store().pinned_ids())} "
                  f"trace(s) as evidence")
        # the same scrape history the detectors graded, as JSON rings —
        # ?name= prefix-filters, ?last=N bounds the window
        ts = _json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{door.port}"
            "/debug/timeseries?name=dl4j_http_requests_total&last=5",
            timeout=10).read())
        for name, pts in sorted(ts["series"].items()):
            vals = ", ".join(f"{v:g}" for _, v in pts)
            print(f"  /debug/timeseries {name}: [{vals}]")
    finally:
        door.stop()
        fleet_reg.shutdown()

    if args.keep_serving:
        print("serving — ctrl-c to exit")
        import time
        while True:
            time.sleep(60)
    server.stop()


if __name__ == "__main__":
    main()
