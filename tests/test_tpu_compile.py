"""Compiles for a TPU v5e that is described, not attached.

The Pallas decode kernels and one speculative-verify decode step, at the
published widths of the models the engine serves, must pass Mosaic's
tiling rules, lower to a real kernel (``tpu_custom_call``, not the
interpreter) and fit one chip's 16 GB. The chip benchmark's megastep must
keep its page pool in place. Nothing runs, so these say nothing about
results or times; they catch refused block shapes, excess memory and
whole-buffer copies at no chip cost.

Only one process at a time may load the TPU runtime, so the topology is
described inside a module fixture, never while a module is imported, and
every test of this kind lives in this one file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.configs.mt import retro_config, with_vocab
from repro.data import SyntheticReactionDataset
from repro.kernels.decode_gqa import ops as decode_ops
from repro.models import attention
from repro.models import seq2seq as s2s
from repro.serving import EngineConfig, StreamingEngine

V5E_HBM_BYTES = 16 * 10**9
ECFG = EngineConfig()                       # the paper's serving shapes
PAGE = 16
# the chip benchmark's mt-retro cell (benchmarks/chip/configs/mt-retro.json)
CELL = EngineConfig(mode="speculative_beam", n_slots=4, n_beams=5,
                    n_drafts=25, draft_len=10, max_new=96, max_src=128,
                    paged=True, page_size=PAGE)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for an unattached chip is written to the persistent cache
    but cannot be read back: keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *args) -> str:
    """Compile ``fn`` for the described chip; assert it fits one chip's
    HBM; return the compiled HLO text."""
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, f"{used} bytes exceed one v5e chip"
    return compiled.as_text()


def _widths(name: str) -> tuple[int, int, int]:
    cfg = retro_config() if name == "mt-retro" else get_config(name)
    return cfg.n_heads, cfg.n_kv_heads, cfg.head_dim


def _rows(T: int) -> int:
    """Decode rows a step at query width T serves at the paper's shapes:
    one speculative-beam slot (beams x drafts) verifies DL+1 tokens, the
    plain modes decode one token for a handful of slots."""
    return ECFG.n_beams * ECFG.n_drafts if T > 1 else 4 * ECFG.n_beams


# mt-retro (8 heads over 8 KV heads) at the speculative verify width and
# at one token; smollm-135m (9 query heads over 3 KV heads: G = 3)
WIDTHS = [("mt-retro", ECFG.draft_len + 1), ("mt-retro", 1),
          ("smollm-135m", 1), ("smollm-135m", 9)]


@pytest.mark.parametrize("name,T", WIDTHS)
def test_paged_decode_kernel_compiles(one_chip, name, T):
    H, Kv, hd = _widths(name)
    B = _rows(T)
    n_blocks = -(-(ECFG.max_new + ECFG.draft_len + 2) // PAGE)
    P = B * n_blocks + 1

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hlo = _compile(
        lambda *a: decode_ops.paged_decode_gqa_attention(*a, interpret=False),
        s((B, T, H, hd)), s((P, PAGE, Kv * hd)), s((P, PAGE, Kv * hd)),
        s((P, PAGE), jnp.int32), s((B, n_blocks), jnp.int32),
        s((B, T), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("name,T", [WIDTHS[0], WIDTHS[2]])
def test_dense_decode_kernel_compiles(one_chip, name, T):
    H, Kv, hd = _widths(name)
    B, S = _rows(T), 128

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hlo = _compile(
        lambda *a: decode_ops.decode_gqa_attention(*a, interpret=False),
        s((B, T, H, hd)), s((B, S, Kv, hd)), s((B, S, Kv, hd)),
        s((B, S), jnp.int32), s((B, T), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_mt_retro_paged_verify_step_compiles(one_chip, monkeypatch):
    """One mt-retro speculative-verify decode step (DL+1 tokens for every
    beam x draft row of a speculative-beam slot) over a paged cache, with
    the Pallas paged kernel on, on ``jax.eval_shape`` parameters."""
    tok = SyntheticReactionDataset(1, seed=0, direction="retro").tokenizer
    cfg = with_vocab(retro_config(), tok.vocab_size)
    T = ECFG.draft_len + 1
    B = _rows(T)
    row_len = ECFG.max_new + ECFG.draft_len + 2
    n_pages = B * -(-row_len // PAGE) + 1
    # this process sees the CPU, whose branch would interpret the kernel:
    # steer the kernel path to what a TPU process takes
    monkeypatch.setattr(attention, "_PAGED_KERNEL", True)
    monkeypatch.setattr(decode_ops, "interpret_mode", lambda i=None: False)

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: s2s.init(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(lambda: s2s.init_cache(
        cfg, B, row_len, memory_len=ECFG.max_src,
        memory_mask=jnp.ones((B, ECFG.max_src), bool),
        paged=(n_pages, PAGE))))
    tokens = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=one_chip)

    hlo = _compile(
        lambda p, c, t, pos: s2s.decode_step(p, cfg, c, t, pos),
        params, cache, tokens, tokens)
    assert "tpu_custom_call" in hlo


def test_mt_retro_sharded_megastep_compiles_for_four_chips(topo,
                                                          monkeypatch):
    """The serving megastep of an mt-retro engine sharded over a (2, 2)
    mesh of four v5e chips: speculative and speculative-beam groups of
    two slots each at the paper's shapes. The engine places its params
    and state with ``jax.device_put``, which cannot reach a described
    chip: hand it the shapes with their shardings instead."""
    monkeypatch.setattr(jax, "device_put", lambda tree, shardings: (
        jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                       sharding=s),
                     tree, shardings)))
    tok = SyntheticReactionDataset(1, seed=0, direction="retro").tokenizer
    cfg = with_vocab(retro_config(), tok.vocab_size)
    params = jax.eval_shape(lambda: s2s.init(jax.random.PRNGKey(0), cfg))
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    eng = StreamingEngine(params, cfg, tok, EngineConfig(
        paged=True, page_size=PAGE,
        mode_groups={"speculative": 2, "speculative_beam": 2}, mesh=mesh))
    compiled = eng._megastep_fn.lower(eng.params,
                                      eng.scheduler.state).compile()
    mem = compiled.memory_analysis()      # per device
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < V5E_HBM_BYTES)
    assert "all-reduce" in compiled.as_text()


# -- reading a compiled HLO module ------------------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%(\S+) = (.*)$")
_CALLS = re.compile(r"(?:calls|body|condition|to_apply|true_computation|"
                    r"false_computation)=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_ARRAY = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
# a branch made only of these passes its operands through unchanged
_PASS_THROUGH = {"parameter", "get-tuple-element", "tuple", "copy",
                 "copy-start", "copy-done", "bitcast", "constant"}


def _shape_and_op(rest: str) -> tuple[str, str]:
    """Split ``<shape> <opcode>(...)`` of an instruction line; a tuple
    shape is parenthesised."""
    if rest.startswith("("):
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, tail = rest[:end + 1], rest[end + 2:]
    else:
        shape, _, tail = rest.partition(" ")
    return shape, tail.partition("(")[0]


def _computations(hlo: str) -> tuple[dict, str]:
    """{computation: [(name, is_root, shape, opcode, line)]}, entry name."""
    comps, entry, cur = {}, None, None
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            entry = m.group(1) if line.startswith("ENTRY") else entry
            continue
        m = _INSTRUCTION.match(line)
        if m and cur is not None:
            shape, op = _shape_and_op(m.group(3))
            cur.append((m.group(2), bool(m.group(1)), shape, op, line))
    return comps, entry


def _buffers_moved(hlo: str, ops: set, n_elements: set) -> list:
    """Instructions of opcode in ``ops`` (a fusion counts as its root's
    opcode) whose output holds an array of one of ``n_elements``, on every
    path from the entry except conditional branches that only pass their
    operands through."""
    comps, entry = _computations(hlo)

    def root_op(name):
        return next(op for _, root, _, op, _ in comps[name] if root)

    seen, todo, found = set(), [entry], []
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for name, _, shape, op, line in comps[comp]:
            called = _CALLS.findall(line)
            branches = _BRANCHES.search(line)
            if branches:
                called += [b.strip().lstrip("%")
                           for b in branches.group(1).split(",")]
            if op == "conditional":
                called = [c for c in called if not all(
                    i[3] in _PASS_THROUGH for i in comps[c])]
            if op == "fusion":
                op, called = root_op(called[0]), []
            todo += called
            sizes = {int(np.prod([int(d) for d in dims.split(",") if d]))
                     for dims in _ARRAY.findall(shape)}
            if op in ops and sizes & n_elements:
                found.append(f"{comp}: {name} {op} {shape[:60]}")
    return found


def test_mt_retro_megastep_keeps_page_pool_in_place(one_chip):
    """The benchmark cell's one-chip megastep (mt-retro, speculative beam,
    four slots at the paper's shapes): the self-attention page pool is
    written in place by layer-indexed scatters. Outside the pool-exhausted
    identity branch no copy, dynamic-slice or dynamic-update-slice makes a
    buffer the size of a layer's pool or of the stacked pool, and the
    step's temporaries stay under 1.5 GB (6.0 GB when each layer's pool
    was sliced out of the stack, written back, and the stack relaid out
    for the copy-on-write page scatter)."""
    tok = SyntheticReactionDataset(1, seed=0, direction="retro").tokenizer
    cfg = with_vocab(retro_config(), tok.vocab_size)
    params = jax.eval_shape(lambda: s2s.init(jax.random.PRNGKey(0), cfg))
    eng = StreamingEngine(params, cfg, tok, CELL)

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    compiled = eng._megastep_fn.lower(
        on_chip(eng.params), on_chip(eng.scheduler.state)).compile()
    pool = eng.scheduler.state.cache["self"].k_pool
    assert pool.shape[1:] == (eng._paged_geometry()[0], PAGE,
                              cfg.n_kv_heads * cfg.head_dim)
    moved = _buffers_moved(
        compiled.as_text(),
        {"copy", "copy-start", "dynamic-slice", "dynamic-update-slice"},
        {pool.size // pool.shape[0], pool.size})
    assert not moved, "whole-pool moves in the megastep:\n" + "\n".join(moved)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
