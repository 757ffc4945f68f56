"""ModelBackend invariants: decoder-only serving through StreamingEngine.

The contract that makes architecture-agnostic serving safe to ship:

  1. decoder-only greedy/speculative serving through the StreamingEngine
     (chunked ragged prefill, recycled slots, shared jitted step) is
     token-identical to the one-shot ``greedy_decode`` /
     ``speculative_greedy_decode`` paths (monolithic ``tr.prefill``) —
     for attention AND recurrent architectures;
  2. the identity survives the paged decoder-only cache, including under
     forced page exhaustion + preemption (a preempted mid-prefill request
     replays its whole chunk plan deterministically);
  3. a ragged stream of prompt lengths causes ZERO recompilation after one
     warmup request per group — prompt length only changes the chunk
     COUNT, on the host;
  4. the chunk size is invisible: chunk=3 and chunk=max_src sessions emit
     identical tokens;
  5. the explicit ``Seq2SeqBackend`` is the engine's default for seq2seq
     configs and keeps the encoder-decoder admission monolithic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (beam_search, greedy_decode, prompt_lookup_drafts,
                        speculative_beam_search, speculative_greedy_decode,
                        transformer_handle)
from repro.models import transformer as tr
from repro.serving import (DecoderOnlyBackend, EngineConfig, Seq2SeqBackend,
                           StreamingEngine, make_backend)

MAX_NEW = 12
MAX_SRC = 28
DL, ND = 4, 5
EOS = 2
# dense GQA + attention-free recurrent: the two ends of the architecture
# space the backend must serve identically
ARCHS = ["smollm-135m", "rwkv6-1.6b"]


@pytest.fixture(scope="module", params=ARCHS)
def decoder_model(request):
    cfg = get_config(request.param, reduced=True)
    params = tr.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    # ragged lengths, incl. a one-token prompt (zero prefill chunks) and a
    # partial final chunk for every chunk size under test
    lens = [9, 17, 24, 1, 21, 5]
    return [rng.integers(4, 500, size=L).astype(np.int32) for L in lens]


def _one_shot(cfg, params, prompt, mode):
    handle = transformer_handle(params, cfg)
    P = len(prompt)
    cache = tr.init_cache(cfg, 1, P + MAX_NEW + DL + 4)
    if P > 1:
        _, cache = tr.prefill(params, cfg, cache,
                              jnp.asarray(prompt[None, :-1]))
    last = jnp.asarray([prompt[-1]])
    pos = jnp.asarray([P - 1], jnp.int32)
    if mode == "greedy":
        r = greedy_decode(handle, cache, last, pos, max_new=MAX_NEW,
                          eos_id=EOS)
    else:
        d, m = prompt_lookup_drafts(prompt, DL, ND)
        r = speculative_greedy_decode(
            handle, cache, last, pos, jnp.asarray(d[None]),
            jnp.asarray(m[None]), max_new=MAX_NEW, eos_id=EOS)
    return np.asarray(r.tokens[0])


def _engine(cfg, params, mode, **kw):
    base = dict(mode=mode, draft_len=DL, n_drafts=ND, max_new=MAX_NEW,
                max_src=MAX_SRC, n_slots=2, prefill_chunk=5, eos_id=EOS)
    base.update(kw)
    return StreamingEngine(params, cfg, None, EngineConfig(**base))


# ---------------------------------------------------------------------------
# 1. streaming == one-shot, ragged prompts, every arch


@pytest.mark.parametrize("mode", ["greedy", "speculative"])
def test_decoder_streaming_matches_one_shot(decoder_model, prompts, mode):
    cfg, params = decoder_model
    want = [_one_shot(cfg, params, p, mode) for p in prompts]
    eng = _engine(cfg, params, mode)
    # staggered arrivals: admissions (and their prefill chunks) interleave
    # with strangers' decode steps in recycled slots
    rids = [eng.submit(p, arrival=float(i)) for i, p in enumerate(prompts)]
    res = eng.serve()
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(np.asarray(res[rid].tokens[0]), w)


def _one_shot_beam(cfg, params, prompt, mode, n_beams):
    """One-shot decoder-only beam / speculative-beam reference: monolithic
    prefill of the prompt into a 1-row cache, then the batched beam loop
    (expanded internally to n_beams * N_d rows)."""
    handle = transformer_handle(params, cfg)
    P = len(prompt)
    cache = tr.init_cache(cfg, 1, P + MAX_NEW + DL + 4)
    if P > 1:
        _, cache = tr.prefill(params, cfg, cache,
                              jnp.asarray(prompt[None, :-1]))
    if mode == "beam":
        r = beam_search(handle, cache, int(prompt[-1]), P - 1,
                        n_beams=n_beams, max_new=MAX_NEW, eos_id=EOS)
    else:
        d, m = prompt_lookup_drafts(prompt, DL, ND)
        r = speculative_beam_search(
            handle, cache, int(prompt[-1]), P - 1, jnp.asarray(d),
            jnp.asarray(m), n_beams=n_beams, max_new=MAX_NEW, eos_id=EOS)
    return np.asarray(r.tokens), np.asarray(r.logprobs)


@pytest.mark.parametrize("mode", ["beam", "speculative_beam"])
def test_decoder_beam_streaming_matches_one_shot(decoder_model, prompts,
                                                 mode):
    """ROADMAP follow-on: the beam-family machinery has run in decoder-only
    mode groups since PR 4 but only greedy/speculative were identity-tested.
    Engine beam / spec-beam serving (chunked prefill, sibling rows adopting
    row 0, recycled slots) must match the one-shot beam loops beam for
    beam."""
    cfg, params = decoder_model
    K = 3
    want = [_one_shot_beam(cfg, params, p, mode, K) for p in prompts]
    eng = _engine(cfg, params, mode, n_beams=K)
    rids = [eng.submit(p, arrival=float(i)) for i, p in enumerate(prompts)]
    res = eng.serve()
    for rid, (toks, logp) in zip(rids, want):
        np.testing.assert_array_equal(np.asarray(res[rid].tokens), toks)
        np.testing.assert_allclose(np.asarray(res[rid].logprobs), logp,
                                   rtol=1e-5, atol=1e-5)


def test_chunk_size_is_invisible(decoder_model, prompts):
    """Chunked and monolithic prefill admit identical requests."""
    cfg, params = decoder_model
    tiny = _engine(cfg, params, "speculative", prefill_chunk=3)
    whole = _engine(cfg, params, "speculative", prefill_chunk=MAX_SRC)
    ra = [tiny.submit(p) for p in prompts]
    rb = [whole.submit(p) for p in prompts]
    res_a, res_b = tiny.serve(), whole.serve()
    for a, b in zip(ra, rb):
        np.testing.assert_array_equal(np.asarray(res_a[a].tokens),
                                      np.asarray(res_b[b].tokens))


# ---------------------------------------------------------------------------
# 2. paged decoder-only cache: identity + forced exhaustion/preemption


def _paged_model():
    cfg = get_config("smollm-135m", reduced=True)
    return cfg, tr.init(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("mode", ["greedy", "speculative", "beam",
                                  "speculative_beam"])
def test_decoder_paged_matches_dense(prompts, mode):
    """Paged decoder-only serving is token-identical to dense in every
    mode; the beam modes reorder rows over shared pages and copy-on-write
    the draft-boundary page every step, in every layer of the pool."""
    cfg, params = _paged_model()
    kw = dict(n_beams=3) if "beam" in mode else {}
    dense = _engine(cfg, params, mode, **kw)
    paged = _engine(cfg, params, mode, paged=True, page_size=8, **kw)
    pool = paged.scheduler.state.cache[0].k_pool
    assert pool.shape[0] == cfg.n_repeats
    assert pool.shape[2:] == (8, cfg.n_kv_heads * cfg.head_dim)
    rd = [dense.submit(p) for p in prompts]
    rp = [paged.submit(p) for p in prompts]
    res_d, res_p = dense.serve(), paged.serve()
    for a, b in zip(rd, rp):
        np.testing.assert_array_equal(np.asarray(res_d[a].tokens),
                                      np.asarray(res_p[b].tokens))
        np.testing.assert_allclose(np.asarray(res_d[a].logprobs),
                                   np.asarray(res_p[b].logprobs),
                                   rtol=1e-5, atol=1e-5)
    paged.allocator.check()
    fp = paged.cache_footprint()
    assert fp["peak_bytes"] <= fp["capacity_bytes"]


def test_decoder_paged_exhaustion_preempts_never_corrupts(prompts):
    """A pool barely above one slot's worst case serving 3 slots: chunked
    prefills and resident decodes fight over pages, residents (and
    mid-prefill admissions) get preempted, and every request still
    finishes token-identical to the dense run."""
    cfg, params = _paged_model()
    dense = _engine(cfg, params, "speculative", n_slots=3)
    spec = dense.spec
    ps = 8
    be = DecoderOnlyBackend(cfg, dense.ecfg, None)
    need = be.prefill_blocks(ps) + spec.rows_per_slot * (
        -(-spec.cache_len // ps) + 1)
    paged = _engine(cfg, params, "speculative", n_slots=3, paged=True,
                    page_size=ps, n_pages=1 + need + 3)
    fp = paged.cache_footprint()
    assert paged.n_slots > fp["contiguous_equiv_slots"], \
        "pool must be smaller than the contiguous-row layout would need"
    rd = [dense.submit(p) for p in prompts]
    rp = [paged.submit(p) for p in prompts]
    res_d, res_p = dense.serve(), paged.serve()
    assert paged.scheduler.n_preemptions > 0, \
        "pool sized to exercise preemption, but none happened"
    for a, b in zip(rd, rp):
        np.testing.assert_array_equal(np.asarray(res_d[a].tokens),
                                      np.asarray(res_p[b].tokens))
    paged.allocator.check()


def test_minimum_pool_admits_and_completes(prompts):
    """Regression: a pool sized EXACTLY to one slot's validated worst case
    must still admit (admit_pages_for is clamped to that bound) — an empty
    pool that can never admit would livelock serve() with the queue
    non-empty and nothing resident to preempt."""
    cfg, params = _paged_model()
    probe = _engine(cfg, params, "greedy", paged=True, page_size=16)
    need = probe.allocator._slot_worst["greedy"]
    assert probe.allocator.admit_pages_for("greedy") <= need
    tight = _engine(cfg, params, "greedy", paged=True, page_size=16,
                    n_pages=1 + need)
    dense = _engine(cfg, params, "greedy")
    rt = [tight.submit(p) for p in prompts[:3]]
    rd = [dense.submit(p) for p in prompts[:3]]
    res_t, res_d = tight.serve(), dense.serve()
    for a, b in zip(rt, rd):
        np.testing.assert_array_equal(np.asarray(res_t[a].tokens),
                                      np.asarray(res_d[b].tokens))
    tight.allocator.check()


# ---------------------------------------------------------------------------
# 3. zero recompilation across a ragged prompt stream


def test_decoder_zero_recompile_after_warmup(prompts):
    cfg, params = _paged_model()
    eng = _engine(cfg, params, "speculative")
    eng.submit(prompts[0])
    eng.serve()
    eng.reset()
    warm = dict(eng.n_traces)
    assert warm["step"] == 1
    # the prefill-carrying megastep variant traces once too (chunk writes
    # ride inside the fused step now — there is no separate chunk jit)
    assert warm["step_prefill"] == 1
    for key in ("admit", "finish"):
        assert warm[key, "speculative"] == 1, (key, warm)

    # ragged lengths over recycled slots: chunk counts vary, traces don't
    for i, p in enumerate(prompts):
        eng.submit(p, arrival=float(i % 3))
    res = eng.serve()
    assert len(res) == len(prompts)
    assert dict(eng.n_traces) == warm, \
        f"ragged decoder traffic retraced after warmup: {warm} -> {eng.n_traces}"


# ---------------------------------------------------------------------------
# 4. backend selection + seq2seq explicitness


def test_make_backend_routes_on_family():
    cfg = get_config("smollm-135m", reduced=True)
    ecfg = EngineConfig()
    assert isinstance(make_backend(cfg, ecfg, None), DecoderOnlyBackend)
    from repro.configs.mt import tiny_config
    from repro.data import SyntheticReactionDataset
    ds = SyntheticReactionDataset(4, seed=0)
    mt = tiny_config(ds.tokenizer.vocab_size, depth=1, d_model=32)
    assert isinstance(make_backend(mt, ecfg, ds.tokenizer), Seq2SeqBackend)
    with pytest.raises(ValueError):
        DecoderOnlyBackend(mt, ecfg, None)          # seq2seq family
    with pytest.raises(ValueError):
        Seq2SeqBackend(cfg, ecfg, None)             # tokenizer required


def test_unpageable_arch_rejected():
    """Attention-free archs have no K/V to page — a paged session is a
    config error, not a silent dense fallback."""
    cfg = get_config("rwkv6-1.6b", reduced=True)
    params = tr.init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError):
        _engine(cfg, params, "greedy", paged=True)


def test_prompt_length_bounds_enforced():
    cfg, params = _paged_model()
    eng = _engine(cfg, params, "greedy")
    with pytest.raises(ValueError):
        eng.submit(np.zeros((0,), np.int32))        # empty prompt
    with pytest.raises(ValueError):
        eng.submit(np.arange(MAX_SRC + 1, dtype=np.int32) + 4)  # too long
