"""Serving launcher: speculative decoding on any decoder-only architecture
(prompt-lookup drafting) or the Molecular Transformer (source-copy drafting
via the serving engines — see examples/serve_retrosynthesis.py).

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --reduced \
        --requests 4 --max-new 48

Runs the one-shot greedy vs speculative comparison, then the continuous
serving pass: the same requests stream through a ``StreamingEngine`` on the
``DecoderOnlyBackend`` (``repro.serving.backend``) via the request front
door (``repro.serving.api``) — ragged prompts admitted by chunked prefill
into fixed decode slots, one jitted step for the whole run, optional paged
KV cache (``--paged``). Request 0's tokens are consumed INCREMENTALLY
through ``handle.stream()`` while the other slots keep decoding, one extra
request demonstrates per-request ``GenerationParams`` (a private token
budget under the session ceiling) + ``cancel()``, and every engine output
is asserted token-identical to the one-shot speculative pass, which is
itself asserted identical to greedy. Skip the serving pass with
--no-continuous.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import (greedy_decode, prompt_lookup_drafts,
                        speculative_greedy_decode, transformer_handle)
from repro.launch.mesh import make_serving_mesh
from repro.launch.runtime import enable_compile_cache
from repro.models import transformer as tr
from repro.serving import (EngineConfig, GenerationParams, RequestCancelled,
                           StreamingEngine)

EOS_ID = 2


def continuous_demo(params, cfg, prompts, args, expected=None) -> None:
    """Decoder-only continuous batching through the StreamingEngine: each
    prompt streams into a freed slot by chunked prefill (no per-admission
    scratch cache), interleaved with the resident slots' decode steps."""
    prompts = np.asarray(prompts)
    B, P = prompts.shape
    mesh = None
    n_slots = min(args.slots, B)
    if args.mesh is not None:
        data, model = args.mesh
        mesh = make_serving_mesh((data, model))
        # every mode group's slot count must split evenly across the data
        # shards — round up rather than reject the CLI's request count
        n_slots = -(-n_slots // data) * data
    ecfg = EngineConfig(
        mode="speculative", draft_len=args.draft_len, n_drafts=args.n_drafts,
        max_new=args.max_new, max_src=P, n_slots=n_slots,
        prefill_chunk=args.prefill_chunk, eos_id=EOS_ID,
        paged=args.paged, page_size=args.page_size, mesh=mesh)
    eng = StreamingEngine(params, cfg, None, ecfg)
    # stagger arrivals so admissions interleave with running decodes
    handles = [eng.submit(row, arrival=float(3 * i))
               for i, row in enumerate(prompts)]
    # per-request params: a low-budget probe sharing the session, plus a
    # cancelled request that never runs (queued -> dequeued)
    probe = eng.submit(prompts[0],
                       params=GenerationParams(max_new=args.max_new // 2))
    doomed = eng.submit(prompts[0], arrival=float(3 * B))
    assert doomed.cancel() and doomed.status == "cancelled"
    t0 = time.time()
    # request 0 consumed incrementally: each delta is committed tokens from
    # one scheduler iteration (the other slots decode in between)
    deltas = list(handles[0].stream())
    results = eng.serve()      # drain the rest of the queue
    dt = time.time() - t0
    ok = [r for r in results.values() if r.status == "finished"]
    acc = sum(r.accepted for r in ok)
    gen = sum(int(r.lengths[0]) for r in ok)
    print(f"continuous  : {B + 1} requests over {ecfg.n_slots} slots "
          f"({'paged' if args.paged else 'dense'} cache, "
          f"chunk={ecfg.prefill_chunk}), {eng.scheduler.n_steps} steps, "
          f"{dt:.2f}s, acceptance={acc / max(gen, 1):.2f}, "
          f"{len(deltas)} stream deltas for request 0")
    r0 = handles[0].result()
    np.testing.assert_array_equal(
        np.concatenate(deltas) if deltas else np.zeros((0,), np.int32),
        r0.tokens[0][:int(r0.lengths[0])])
    assert int(probe.result().lengths[0]) <= args.max_new // 2
    try:
        doomed.result()
        raise AssertionError("cancelled request returned a result")
    except RequestCancelled:
        pass
    if expected is not None:
        for h, want in zip(handles, expected):
            np.testing.assert_array_equal(
                np.asarray(results[h].tokens[0]), np.asarray(want))
        print("continuous == one-shot speculative: True "
              "(stream deltas == committed tokens)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=48)
    ap.add_argument("--draft-len", type=int, default=8)
    ap.add_argument("--n-drafts", type=int, default=16)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--paged", action="store_true",
                    help="serve through a paged KV cache (attention archs)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--mesh", type=int, nargs=2, metavar=("DATA", "MODEL"),
                    help="serve the continuous pass on a (data, model) "
                         "device mesh — slots/pages shard over DATA, params "
                         "over MODEL. Needs DATA*MODEL devices: real "
                         "accelerators are used as they are; for a CPU "
                         "rehearsal only, set XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=N before launch")
    ap.add_argument("--no-continuous", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    if cfg.family == "audio":
        raise SystemExit("encoder-only architecture: no decode step "
                         "(DESIGN.md §4)")
    params = tr.init(jax.random.PRNGKey(0), cfg)
    handle = transformer_handle(params, cfg)
    B, P = args.requests, args.prompt_len
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, P), 4,
                                 cfg.vocab_size)

    def fresh():
        c = tr.init_cache(cfg, B, P + args.max_new + args.draft_len + 4)
        _, c = tr.prefill(params, cfg, c, prompts[:, :-1])
        return c

    last = prompts[:, -1]
    pos = jnp.full((B,), P - 1, jnp.int32)
    t0 = time.time()
    g = greedy_decode(handle, fresh(), last, pos, max_new=args.max_new,
                      eos_id=EOS_ID)
    jax.block_until_ready(g.tokens)
    t_g = time.time() - t0

    ds, ms = zip(*(prompt_lookup_drafts(np.asarray(r), args.draft_len,
                                        args.n_drafts) for r in prompts))
    t0 = time.time()
    s = speculative_greedy_decode(
        handle, fresh(), last, pos,
        jnp.stack([jnp.asarray(d) for d in ds]),
        jnp.stack([jnp.asarray(m) for m in ms]),
        max_new=args.max_new, eos_id=EOS_ID)
    jax.block_until_ready(s.tokens)
    t_s = time.time() - t0

    print(f"arch={cfg.name} B={B} prompt={P} max_new={args.max_new}")
    print(f"greedy      : {int(g.n_calls)} calls, {t_g:.2f}s")
    print(f"speculative : {int(s.n_calls)} calls, {t_s:.2f}s "
          f"acceptance={float(s.acceptance_rate.mean()):.2f}")
    identical = bool((g.tokens == s.tokens).all())
    print(f"outputs identical: {identical}")
    assert identical, "speculative decoding changed the greedy tokens"
    if not args.no_continuous:
        continuous_demo(params, cfg, prompts, args,
                        expected=np.asarray(s.tokens))


if __name__ == "__main__":
    main()
