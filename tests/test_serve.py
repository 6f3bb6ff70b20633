"""Serve-engine tests (ISSUE 9): paged pool + FreeList unit behavior,
Pallas decode-attention bit-parity vs the jnp reference, prefill-vs-
stepwise token parity at the program level, continuous-vs-isolated
token parity across every servable family (dense/GQA, moe, ssm,
hybrid), static policy, backpressure, refusals, checkpoint->serve
handoff, and the kind="step" trace schema."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import io as ckpt_io
from repro.configs.base import get_config
from repro.kernels import decode_attention as da
from repro.models import build_model
from repro.obs import report
from repro.obs.trace import Trace
from repro.optim.packing import layout_of, pack
from repro.serve import (Engine, EngineConfig, Request, paging,
                         restore_params)
from repro.serve import decode as sdecode

SERVE_ARCHS = ("qwen3-32b", "granite-moe-1b-a400m", "xlstm-1.3b",
               "zamba2-7b")


def _requests(cfg, n=6, seed=0, prompt=(2, 10), gen=(2, 7)):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(
                        0, cfg.vocab_size,
                        size=int(rng.integers(*prompt))).astype(np.int32),
                    max_new=int(rng.integers(*gen)))
            for i in range(n)]


def _run_isolated(model, params, reqs, **ecfg):
    eng = Engine(model, params, EngineConfig(n_slots=1, **ecfg))
    out = {}
    for r in reqs:
        done = eng.run([Request(r.rid, r.prompt.copy(), r.max_new)])
        out[r.rid] = done[0].tokens
    return out


# -- paging / FreeList --------------------------------------------------


def test_freelist_never_hands_out_trash_and_backpressures():
    fl = paging.FreeList(6)
    a = fl.alloc(3)
    assert paging.TRASH_ROW not in a.tolist()
    assert fl.alloc(3) is None          # only 2 rows left: defer, not split
    assert fl.available() == 2
    fl.free(a)
    assert fl.available() == 5
    b = fl.alloc(5)
    assert sorted(b.tolist()) == [1, 2, 3, 4, 5]


def test_geom_rows_and_pool_alignment():
    g = paging.make_geom(page_size=4, n_kv=2, head_dim=16, n_layers_kv=3,
                         max_len=10, state_size=1000, n_slots=2)
    assert g.page_elems % paging.ALIGN == 0
    assert g.max_blocks == 3            # ceil(10 / 4)
    assert g.kv_rows_per_slot == 2 * 3 * 3
    assert g.state_rows == -(-1000 // g.page_elems)
    assert g.n_pages == 1 + 2 * g.rows_per_slot
    assert g.pool().shape == (g.n_pages, g.page_elems)


def test_token_kv_write_masks_to_trash():
    g = paging.make_geom(page_size=2, n_kv=1, head_dim=4, n_layers_kv=1,
                         max_len=4, state_size=0, n_slots=2)
    pool = g.pool()
    rows = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    vec = jnp.ones((2, 1, 4), jnp.float32)    # (B, n_kv, head_dim)
    blk = jnp.asarray([0, 1], jnp.int32)
    off = jnp.asarray([1, 0], jnp.int32)
    out = paging.write_token_kv(pool, rows, blk, off, vec,
                                valid=jnp.asarray([True, False]))
    assert float(out[1, 4:8].sum()) == 4.0    # slot 0: row 1, offset 1
    assert float(out[4].sum()) == 0.0         # slot 1 masked -> trash
    assert float(out[0, :4].sum()) == 4.0     # garbage parked on trash row


def test_state_roundtrip_and_trash_masking():
    g = paging.make_geom(page_size=2, n_kv=1, head_dim=4, n_layers_kv=0,
                         max_len=4, state_size=300, n_slots=2)
    pool = g.pool()
    rows = jnp.arange(1, 1 + 2 * g.state_rows, dtype=jnp.int32
                      ).reshape(2, g.state_rows)
    buf = jnp.arange(2 * 300, dtype=jnp.float32).reshape(2, 300)
    pool = paging.write_state(pool, rows, buf)
    got = paging.read_state(pool, rows, 300)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(buf))
    masked = paging.write_state(g.pool(), rows, buf,
                                valid=jnp.asarray([False, True]))
    assert float(masked[rows[0, 0]].sum()) == 0.0
    assert float(masked[rows[1, 0]].sum()) > 0.0


# -- Pallas decode kernel vs jnp reference ------------------------------


@pytest.mark.parametrize("B,n_kv,g,hd,ps,nblk", [
    (4, 2, 2, 8, 4, 5),      # GQA
    (3, 4, 1, 16, 8, 3),     # MHA
    (1, 1, 8, 32, 4, 2),     # MQA-ish, single row
])
def test_paged_decode_kernel_bit_identical_to_ref(B, n_kv, g, hd, ps,
                                                  nblk):
    rng = np.random.default_rng(42)
    H = n_kv * g
    used = ps * n_kv * hd
    n_pages = 1 + 2 * B * nblk
    pool = jnp.asarray(rng.standard_normal(
        (n_pages, ((used + 255) // 256) * 256)).astype(np.float32))
    rows = rng.permutation(np.arange(1, n_pages)).astype(np.int32)
    rows_k = jnp.asarray(rows[:B * nblk].reshape(B, nblk))
    rows_v = jnp.asarray(rows[B * nblk:].reshape(B, nblk))
    q = jnp.asarray(rng.standard_normal((B, H, hd)).astype(np.float32))
    lengths = jnp.asarray(rng.integers(1, ps * nblk + 1, size=B), jnp.int32)
    out_k = da.paged_decode_attention(q, pool, rows_k, rows_v, lengths,
                                      page_size=ps, n_kv=n_kv,
                                      interpret=True)
    out_r = da.paged_decode_attention_ref(q, pool, rows_k, rows_v, lengths,
                                          page_size=ps, n_kv=n_kv)
    assert np.array_equal(np.asarray(out_k), np.asarray(out_r)), (
        np.abs(np.asarray(out_k) - np.asarray(out_r)).max())


def test_decode_ref_ignores_pages_past_length():
    """Length masking means garbage beyond ``lengths`` never leaks."""
    rng = np.random.default_rng(0)
    used = 4 * 2 * 8
    pool = jnp.asarray(rng.standard_normal((9, 256)).astype(np.float32))
    rows_k = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    rows_v = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
    q = jnp.asarray(rng.standard_normal((1, 4, 8)).astype(np.float32))
    a = da.paged_decode_attention_ref(q, pool, rows_k, rows_v,
                                      jnp.asarray([6], jnp.int32),
                                      page_size=4, n_kv=2)
    # length 6 / page 4: only blocks 0,1 are live — trash K blocks 2,3
    # (rows 3,4) and V blocks 2,3 (rows 7,8) with huge finite garbage
    trashed = pool.at[3:5].set(1e6).at[7:9].set(1e6)
    b = da.paged_decode_attention_ref(q, trashed, rows_k, rows_v,
                                      jnp.asarray([6], jnp.int32),
                                      page_size=4, n_kv=2)
    assert np.array_equal(np.asarray(a), np.asarray(b))


# -- engine parity across families --------------------------------------


@pytest.fixture(scope="module", params=SERVE_ARCHS)
def served(request):
    """Continuous engine (3 slots over 6 requests: slot reuse + queueing)
    vs per-request isolated decode, plus the step-trace records."""
    cfg = get_config(request.param).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = _requests(cfg)
    ecfg = dict(page_size=4, max_prompt=12, max_new=8)
    trace = Trace(None, meta={"launcher": "test"})
    eng = Engine(model, params, EngineConfig(n_slots=3, **ecfg),
                 trace=trace)
    done = eng.run([Request(r.rid, r.prompt.copy(), r.max_new)
                    for r in reqs])
    cont = {c.rid: c.tokens for c in done}
    iso = _run_isolated(model, params, reqs, **ecfg)
    return cfg, model, params, reqs, ecfg, cont, iso, done


def test_continuous_matches_isolated(served):
    cfg, _, _, reqs, _, cont, iso, _ = served
    assert set(cont) == {r.rid for r in reqs}
    for rid in cont:
        assert cont[rid] == iso[rid], (cfg.name, rid)


def test_completions_respect_caps(served):
    cfg, _, _, reqs, ecfg, _, _, done = served
    by_rid = {r.rid: r for r in reqs}
    for c in done:
        assert len(c.tokens) == min(by_rid[c.rid].max_new, ecfg["max_new"])
        assert c.prompt_len == len(by_rid[c.rid].prompt)
        assert all(0 <= t < cfg.vocab_size for t in c.tokens)


def test_static_policy_same_tokens_worse_schedule():
    """Static admission is the same compiled programs — identical tokens,
    batches drain fully before readmission."""
    cfg = get_config("qwen3-32b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = _requests(cfg, n=5)
    ecfg = dict(page_size=4, max_prompt=12, max_new=8)
    stat = Engine(model, params,
                  EngineConfig(n_slots=2, policy="static", **ecfg))
    for r in reqs:
        stat.submit(Request(r.rid, r.prompt.copy(), r.max_new))
    tokens, admitted_nonidle = {}, 0
    while stat.queue or stat.n_active():
        pre_active = stat.n_active()
        rep = stat.step()
        if rep.admitted and pre_active:
            admitted_nonidle += 1
        for c in rep.completions:
            tokens[c.rid] = c.tokens
    iso = _run_isolated(model, params, reqs, **ecfg)
    assert tokens == iso
    # static: admission only ever happens on fully-idle ticks
    assert admitted_nonidle == 0


def test_backpressure_defers_then_completes():
    cfg = get_config("qwen3-32b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    probe = sdecode.geom_for(model, n_slots=2, page_size=4, max_len=16)
    tight = 1 + probe.rows_per_slot     # pool fits exactly ONE request
    eng = Engine(model, params, EngineConfig(
        n_slots=2, page_size=4, max_prompt=8, max_new=8, n_pages=tight))
    reqs = _requests(cfg, n=3, prompt=(2, 8), gen=(2, 5))
    done = eng.run([Request(r.rid, r.prompt.copy(), r.max_new)
                    for r in reqs])
    assert {c.rid for c in done} == {r.rid for r in reqs}

    starved = Engine(model, params, EngineConfig(
        n_slots=1, page_size=4, max_prompt=8, max_new=8, n_pages=2))
    starved.submit(Request(0, np.zeros(1, np.int32), 2))
    with pytest.raises(RuntimeError, match="pool too small"):
        starved.step()


def test_sustained_overload_counts_deferrals(tmp_path):
    """Backpressure telemetry (DESIGN.md §15): under a pool sized for one
    request and a deep queue, every blocked admission is counted — the
    kind="step" records carry deferred/deferred_total/free_rows, the
    report surfaces them, and the engine still drains to completion."""
    cfg = get_config("qwen3-32b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    probe = sdecode.geom_for(model, n_slots=2, page_size=4, max_len=16)
    tight = 1 + probe.rows_per_slot     # pool fits exactly ONE request
    path = tmp_path / "overload.jsonl"
    trace = Trace(str(path), meta={"launcher": "serve", "arch": cfg.name})
    eng = Engine(model, params, EngineConfig(
        n_slots=2, page_size=4, max_prompt=8, max_new=8, n_pages=tight),
        trace=trace)
    reqs = _requests(cfg, n=6, prompt=(2, 8), gen=(3, 6))
    done = eng.run([Request(r.rid, r.prompt.copy(), r.max_new)
                    for r in reqs])
    trace.close()
    # sustained overload: with 2 slots and a 1-request pool, the second
    # slot's admissions must have been deferred repeatedly
    assert eng.deferred_total > 0
    assert {c.rid for c in done} == {r.rid for r in reqs}  # nothing lost
    meta, records = report.load(path)
    assert report.check(meta, records) == []
    steps = report.steps_of(records)
    assert all({"deferred", "deferred_total", "free_rows"}
               <= set(s["metrics"]) for s in steps)
    # the cumulative counter is monotone and matches the engine's
    totals = [s["metrics"]["deferred_total"] for s in steps]
    assert totals == sorted(totals)
    assert totals[-1] == eng.deferred_total == sum(
        s["metrics"]["deferred"] for s in steps)
    # the pool was actually exhausted at some point, and recovered
    frees = [s["metrics"]["free_rows"] for s in steps]
    assert min(frees) < probe.rows_per_slot
    assert frees[-1] == eng.free.available()
    s = report.summarize(meta, records)
    assert s["serve"]["deferred_total"] == eng.deferred_total
    assert s["serve"]["free_rows_min"] == min(frees)
    assert s["serve"]["queued_max"] >= 1


def test_serve_trace_schema(tmp_path):
    """kind="step" records pass the obs.report --check gate."""
    cfg = get_config("xlstm-1.3b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    path = tmp_path / "serve.jsonl"
    trace = Trace(str(path), meta={"launcher": "serve", "arch": cfg.name})
    eng = Engine(model, params,
                 EngineConfig(n_slots=2, page_size=4, max_prompt=8,
                              max_new=4), trace=trace)
    eng.run([Request(r.rid, r.prompt.copy(), r.max_new)
             for r in _requests(cfg, n=3, prompt=(2, 8), gen=(2, 5))])
    trace.close()
    meta, records = report.load(path)
    assert report.check(meta, records) == []
    steps = report.steps_of(records)
    assert steps and all("decode_step" in s["phase_s"]
                         or s["metrics"]["admitted"] for s in steps)
    s = report.summarize(meta, records)
    assert s["n_steps"] == len(steps)
    assert "prefill" in s["phase_s"] and "decode_step" in s["phase_s"]


# -- prefill vs stepwise (program level) --------------------------------


@pytest.mark.parametrize("arch", ["qwen3-32b", "zamba2-7b"])
def test_prefill_matches_stepwise_teacher_forcing(arch):
    """prefill(prompt) must emit the same next token as prefilling one
    token and teacher-forcing the rest through the decode step program —
    the whole-prompt path and the incremental path agree."""
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # two slot budgets: one for the whole-prompt path, one for stepwise
    geom = sdecode.geom_for(model, n_slots=2, page_size=4, max_len=12)
    progs = sdecode.build_programs(model, geom)
    fl = paging.FreeList(geom.n_pages)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, size=7).astype(np.int32)

    def slot_tables():
        rows = fl.alloc(geom.rows_per_slot)
        nk = geom.n_layers_kv * geom.max_blocks
        rk = (rows[:nk].reshape(geom.n_layers_kv, geom.max_blocks)
              if nk else np.zeros((1, 1), np.int32))
        rv = (rows[nk:2 * nk].reshape(geom.n_layers_kv, geom.max_blocks)
              if nk else np.zeros((1, 1), np.int32))
        sr = (rows[2 * nk:] if geom.state_rows
              else np.zeros((1,), np.int32))
        return rows, rk, rv, sr

    pool = geom.pool()
    _, rk, rv, sr = slot_tables()
    padded = np.zeros((1, 8), np.int32)
    padded[0, :7] = prompt
    tok_full, pool = progs.prefill(params, pool, padded, np.int32(7),
                                   rk, rv, sr)

    _, rk2, rv2, sr2 = slot_tables()
    first = np.zeros((1, 8), np.int32)
    first[0, 0] = prompt[0]
    _, pool = progs.prefill(params, pool, first, np.int32(1),
                            rk2, rv2, sr2)
    tok = None
    for t in range(1, 7):
        tok, pool = progs.step(
            params, pool, np.asarray([prompt[t]], np.int32),
            np.asarray([t], np.int32), rk2[None], rv2[None], sr2[None],
            np.asarray([True]))
    assert int(np.asarray(tok_full)[0]) == int(np.asarray(tok)[0])


# -- refusals ------------------------------------------------------------


@pytest.mark.parametrize("arch", ["internvl2-1b", "whisper-base"])
def test_unservable_families_refuse(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    with pytest.raises(NotImplementedError, match="serve"):
        sdecode.geom_for(model, n_slots=1, page_size=4, max_len=8)


def test_bad_impl_rejected():
    cfg = get_config("qwen3-32b").reduced()
    model = build_model(cfg)
    geom = sdecode.geom_for(model, n_slots=1, page_size=4, max_len=8)
    with pytest.raises(ValueError):
        sdecode.build_programs(model, geom, impl="cuda")


# -- checkpoint -> serve handoff ----------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("xlstm-1.3b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    return cfg, model, params


def test_handoff_pytree_roundtrip(tiny_model, tmp_path):
    cfg, model, params = tiny_model
    path = str(tmp_path / "ck")
    ckpt_io.save(path, params, metadata={"arch": cfg.name, "rounds": 3})
    got = restore_params(path, model)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_handoff_packed_roundtrip(tiny_model, tmp_path):
    cfg, model, params = tiny_model
    layout = layout_of(params)
    buf = np.asarray(pack(params, layout))
    path = str(tmp_path / "ck_packed")
    # (G, size): per-group buffers are averaged like server_params
    ckpt_io.save(path, {"buf": np.stack([buf, buf])},
                 metadata={"arch": cfg.name})
    got = restore_params(path, model)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)


def test_handoff_arch_mismatch_and_short_buffer(tiny_model, tmp_path):
    cfg, model, params = tiny_model
    path = str(tmp_path / "ck_wrong")
    ckpt_io.save(path, params, metadata={"arch": "qwen3-32b"})
    with pytest.raises(ValueError, match="qwen3-32b"):
        restore_params(path, model)
    restore_params(path, model, check_arch=False)   # explicit override
    short = str(tmp_path / "ck_short")
    ckpt_io.save(short, {"buf": np.zeros(8, np.float32)},
                 metadata={"arch": cfg.name})
    with pytest.raises(ValueError, match="packed checkpoint"):
        restore_params(short, model)
