"""Serving traffic: an open-loop client on the wall clock in front of
the program's continuous-batching engine (``serve.engine.Engine``).

Requests are due at fixed times and are submitted when due, whether or
not earlier ones have finished; between due times the client steps the
engine, and sleeps only when it is empty. Every seed gets one schedule:
Poisson-like due times (the quantiles of the exponential gap at the
traffic's rate) and prompt and output lengths (the quantiles of the
traffic file's log-normals), each in one fixed order; the seed draws
the token ids. So each run is offered the same work at the same times.
A token counts as delivered when the engine's step that produced it
returns.

After the window the engine is freed, and the plain reference runs once
over a sample of the finished requests (drawn from the seed, the longest
among them): each served token's logit is compared with the reference's
best at that position.

Traffic keys: rate_per_s, prompt_len / output_len ({median, sigma, min,
max} of a log-normal), engine ({n_slots, page_size, max_prompt, max_new,
policy, impl}), check ({sample_requests}).
"""
from __future__ import annotations

import gc
import math
import statistics
import time

import numpy as np

import common


def _quantile_sizes(n: int, d: dict) -> np.ndarray:
    """n sizes at the (i + 0.5) / n quantiles of a clipped log-normal."""
    z = [statistics.NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    x = d["median"] * np.exp(d["sigma"] * np.asarray(z))
    return np.clip(np.rint(x), d["min"], d["max"]).astype(np.int64)


def schedule(traffic: dict, seed: int, seconds: float, vocab: int):
    """The requests of one run: (due time s, prompt ids, max_new), in due
    order; enough of them to cover the window at the traffic's rate."""
    rate = traffic["rate_per_s"]
    n = int(math.ceil(rate * seconds * 1.25)) + 8
    # one schedule of arrivals and sizes for every seed, so that each run
    # is offered the same work at the same times; the seed draws the ids
    fixed = common.np_rng(0, common.LANE_TRAFFIC)
    q = (np.arange(n) + 0.5) / n
    gaps = fixed.permutation(-np.log1p(-q) / rate)
    plen = fixed.permutation(_quantile_sizes(n, traffic["prompt_len"]))
    olen = fixed.permutation(_quantile_sizes(n, traffic["output_len"]))
    rng = common.np_rng(seed, common.LANE_TRAFFIC)
    due = np.cumsum(gaps)
    out = []
    for i in range(n):
        ids = rng.integers(0, vocab, size=int(plen[i])).astype(np.int32)
        out.append((float(due[i]), ids, int(olen[i])))
    return out


def pct(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def serve_window(engine, reqs, seconds: float, Request):
    """Drive ``engine`` open-loop for ``seconds``. Returns the record of
    every request sent and of every step."""
    sent, times, done = {}, {}, {}
    steps = []
    lateness = []
    i = 0
    t0 = time.perf_counter()

    def clock():
        return time.perf_counter() - t0

    def deliver(rid, n, t):
        have = len(times[rid])
        times[rid].extend([t] * (n - have))

    with common.annotate("window"):
        while True:
            now = clock()
            if now >= seconds:
                break
            with common.annotate("arrive"):
                while i < len(reqs) and reqs[i][0] <= now:
                    due, ids, max_new = reqs[i]
                    engine.submit(Request(rid=i, prompt=ids,
                                          max_new=max_new, arrival=due))
                    sent[i] = due
                    times[i] = []
                    lateness.append(now - due)
                    i += 1
            if engine.queue or engine.n_active():
                with common.annotate("step"):
                    rep = engine.step()
                t = clock()
                with common.annotate("deliver"):
                    for s in engine.slots:
                        if s is not None:
                            deliver(s.req.rid, len(s.tokens), t)
                    for c in rep.completions:
                        deliver(c.rid, len(c.tokens), t)
                        done[c.rid] = c
                steps.append((rep.prefill_s, rep.decode_s, rep.admitted))
            else:
                nxt = reqs[i][0] if i < len(reqs) else seconds
                with common.annotate("idle"):
                    time.sleep(max(0.0, min(nxt, seconds) - clock()))
    close = clock()
    # due before the close but not yet submitted: they count at their age
    unsent = [reqs[j][0] for j in range(i, len(reqs)) if reqs[j][0] < close]
    return {"t0": t0, "close": close, "sent": sent, "unsent": unsent,
            "times": times,
            "done": done, "steps": steps, "lateness": lateness,
            "prompt_len": {r: len(reqs[r][1]) for r in sent}}


def latency_metrics(w: dict) -> dict:
    close = w["close"]
    ttft, itl = [], []
    for rid, due in w["sent"].items():
        ts = w["times"][rid]
        ttft.append((ts[0] if ts else close) - due)
        itl.extend(b - a for a, b in zip(ts, ts[1:]))
    ttft.extend(close - due for due in w["unsent"])
    # every output token delivered in the window, finished request or not
    out_tokens = sum(len(ts) for ts in w["times"].values())
    nan = float("nan")
    return {"ttft_p50_ms": 1000.0 * pct(ttft, 50),
            "ttft_p95_ms": 1000.0 * pct(ttft, 95),
            "itl_p95_ms": 1000.0 * pct(itl, 95) if itl else nan,
            "itl_p99_ms": 1000.0 * pct(itl, 99) if itl else nan,
            "serve_tokens_per_s": out_tokens / close}


def decode_contexts(w: dict):
    """Resident context of every slot in every decode step of the window:
    a request's j-th decode attends to prompt + j + 1 tokens."""
    out = []
    for rid, ts in w["times"].items():
        p = w["prompt_len"][rid]
        out.extend(p + j + 1 for j in range(max(len(ts) - 1, 0)))
    return out


def sample_requests(w: dict, seed: int, k: int):
    done = sorted(w["done"])
    if not done:
        return []
    longest = max(done, key=lambda r: w["prompt_len"][r]
                  + len(w["done"][r].tokens))
    rng = common.np_rng(seed, common.LANE_SAMPLE)
    rest = [r for r in done if r != longest]
    pick = list(rng.choice(rest, size=min(k - 1, len(rest)),
                           replace=False)) if rest else []
    return [longest] + [int(r) for r in pick]


def reference_gaps(arch, seed, items, length, modes=("f32",)):
    """For each (prompt, served tokens): the reference's logits at every
    position that produced a served token, on one padded (1, length)
    sequence. Returns per mode the widest gap by which the chosen token's
    f32-reference logit lies below the f32 reference's best: for
    ``"f32"`` the served token, for a control mode the token that mode
    puts first."""
    import jax
    import jax.numpy as jnp
    from refs import decoder as ref
    params = common.nest(common.make_weights(ref.param_specs(arch), seed))
    fns = {m: jax.jit(lambda p, t, m=m: _with_precision(
        m, lambda: ref.logits(p, t, arch, m))) for m in set(modes) | {"f32"}}
    worst = {m: 0.0 for m in modes}
    for prompt, served in items:
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        pad = np.zeros((1, length), np.int32)
        pad[0, :len(seq)] = seq
        pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
        lg = np.asarray(fns["f32"](params, jnp.asarray(pad)))[0, pos]
        best = lg.max(-1)
        for m in modes:
            if m == "f32":
                pick = np.asarray(served)
            else:
                lo = np.asarray(fns[m](params, jnp.asarray(pad)))[0, pos]
                pick = lo.argmax(-1)
            gap = best - lg[np.arange(len(pos)), pick]
            worst[m] = max(worst[m], float(gap.max()))
    return worst


def _with_precision(mode, f):
    import jax
    if mode == "f32":
        with jax.default_matmul_precision("highest"):
            return f()
    return f()


def build_engine(cell, seed):
    common.src_on_path()
    import jax
    from repro.models import build_model
    from repro.serve import Engine, EngineConfig
    from refs import decoder as ref
    arch = cell["config_file"]["arch"]
    cfg = common.arch_config(cell["config_file"])
    model = build_model(cfg)
    params = common.nest(common.make_weights(ref.param_specs(arch), seed))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if (jax.tree.map(lambda x: (x.shape, x.dtype), want)
            != jax.tree.map(lambda x: (x.shape, x.dtype), params)):
        raise SystemExit("bench: the weights' tree does not match the "
                         "program's parameters")
    e = cell["traffic_file"]["engine"]
    engine = Engine(model, params, EngineConfig(
        n_slots=e["n_slots"], page_size=e["page_size"],
        max_prompt=e["max_prompt"], max_new=e["max_new"],
        impl=e.get("impl", "auto"), policy=e.get("policy", "continuous")))
    return engine


def run(cell, seed, seconds, trace_dir, devs, counter):
    import jax
    tf = cell["traffic_file"]
    arch = cell["config_file"]["arch"]
    engine = build_engine(cell, seed)
    from repro.serve import Request
    engine.warmup()             # compiles the prefill bucket and the step
    reqs = schedule(tf, seed, seconds, arch["vocab_size"])
    length = engine.bucket + tf["engine"]["max_new"]
    if trace_dir:
        common.start_trace(trace_dir)
    counter.armed = True
    w = serve_window(engine, reqs, seconds, Request)
    counter.armed = False
    if trace_dir:
        jax.profiler.stop_trace()
    device = common.device_record(devs)
    engine.pool = None
    del engine
    gc.collect()
    e2e = latency_metrics(w)
    pick = sample_requests(w, seed, tf["check"]["sample_requests"])
    items = [(reqs[r][1], w["done"][r].tokens) for r in pick]
    gaps = reference_gaps(arch, seed, items, length)
    steps = w["steps"]
    lat = np.asarray(w["lateness"] or [0.0])
    return {
        "t_window": w["t0"],
        "attempted": len(w["sent"]) + len(w["unsent"]), "failed": 0,
        "e2e": e2e, "device": device,
        "checks": {"max_logit_gap": gaps["f32"] if items else None},
        "ctx": {"kind": "serve", "arch": arch, "traffic": tf,
                "window_s": w["close"],
                "prefill_s": sum(s[0] for s in steps),
                "admitted": sum(s[2] for s in steps),
                "decode_s": sum(s[1] for s in steps),
                "decode_steps": sum(1 for s in steps if s[1] > 0),
                "contexts": decode_contexts(w)},
        "readings": {"served_tokens_checked": sum(len(t) for _, t in items),
                     "requests_done": len(w["done"]),
                     "lateness_p50_ms": 1000 * float(np.median(lat)),
                     "lateness_max_ms": 1000 * float(lat.max())},
    }
