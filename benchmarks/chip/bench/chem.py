"""Synthetic reactions and the atomwise SMILES tokenizer, copied from the
program's ``repro.data.synthetic`` / ``repro.data.tokenizer`` so that the
benchmark's queries cannot move when the program changes.

Reactions keep the property the paper exploits: products share long token
substrings with reactants (a scaffold survives the reaction). Forward
(product prediction) maps reactants -> product; retro maps product ->
reactants. The vocabulary is the generator's fixed inventory (28 ids), not
USPTO's ~300: USPTO is not available offline.
"""

from __future__ import annotations

import re

import numpy as np

ATOMWISE_PATTERN = (
    r"(\[[^\]]+]|Br?|Cl?|N|O|S|P|F|I|b|c|n|o|s|p|\(|\)|\.|=|#|-|\+|\\|\/|:"
    r"|~|@|\?|>|\*|\$|\%[0-9]{2}|[0-9])"
)
_TOKEN_RE = re.compile(ATOMWISE_PATTERN)
SPECIALS = ("<pad>", "<bos>", "<eos>", "<unk>")
PAD, BOS, EOS, UNK = range(4)

_CHAIN_ATOMS = ["C", "C", "C", "c", "c", "N", "O", "n", "S"]
_DECOR = ["F", "Cl", "Br", "=O", "C", "OC", "N"]
_BRACKET = ["[nH]", "[C@@H]", "[C@H]", "[O-]", "[N+]"]
FRAGMENTS = [
    "C(=O)OC(C)(C)C",       # Boc
    "C(=O)OCc1ccccc1",      # Cbz
    "S(=O)(=O)C",           # mesyl
    "C(=O)C",               # acetyl
    "Cc1ccccc1",            # benzyl
    "C(F)(F)F",             # CF3
    "OCC",                  # ethoxy
    "N(C)C",                # dimethylamino
]
LEAVING_GROUPS = ["Cl", "Br", "I", "OS(=O)(=O)C"]


def tokenize(smiles: str) -> list[str]:
    tokens = _TOKEN_RE.findall(smiles)
    if "".join(tokens) != smiles:
        raise ValueError(f"SMILES not fully tokenizable: {smiles!r}")
    return tokens


def inventory() -> list[str]:
    """Every token the generator can emit, specials first, then sorted."""
    toks: set[str] = set()
    for s in (_CHAIN_ATOMS + _DECOR + _BRACKET + FRAGMENTS + LEAVING_GROUPS
              + ["%10", "()", ".", "1234"]):
        toks.update(tokenize(s))
    return list(SPECIALS) + sorted(toks - set(SPECIALS))


class Tokenizer:
    """ids: pad 0, bos 1, eos 2, unk 3, then the inventory sorted."""

    pad_id, bos_id, eos_id, unk_id = PAD, BOS, EOS, UNK

    def __init__(self):
        self.itos = inventory()
        self.stoi = {t: i for i, t in enumerate(self.itos)}

    @property
    def vocab_size(self) -> int:
        return len(self.itos)

    def encode(self, smiles: str) -> list[int]:
        return [self.stoi.get(t, UNK) for t in tokenize(smiles)]

    def decode(self, ids) -> str:
        out = []
        for i in ids:
            i = int(i)
            if i == EOS:
                break
            if i not in (PAD, BOS):
                out.append(self.itos[i])
        return "".join(out)


def _random_scaffold(rng: np.random.Generator, n_atoms: int) -> str:
    out: list[str] = []
    ring_open = False
    ring_digit = str(rng.integers(1, 5))
    aromatic_run = 0
    i = 0
    while i < n_atoms:
        a = _CHAIN_ATOMS[rng.integers(len(_CHAIN_ATOMS))]
        if aromatic_run > 0:
            a = "c"
            aromatic_run -= 1
        out.append(a)
        if not ring_open and a == "c" and rng.random() < 0.6 and i + 5 < n_atoms:
            out.append(ring_digit)
            ring_open = True
            aromatic_run = 5
            ring_close_at = i + 5
        elif ring_open and i == ring_close_at:
            out.append(ring_digit)
            ring_open = False
        if rng.random() < 0.25 and not aromatic_run:
            out.append("(")
            out.append(_DECOR[rng.integers(len(_DECOR))])
            out.append(")")
        if rng.random() < 0.06 and not aromatic_run:
            out.append(_BRACKET[rng.integers(len(_BRACKET))])
            i += 1
        i += 1
    if ring_open:
        out.append("c")
        out.append(ring_digit)
    return "".join(out)


def make_reaction(rng: np.random.Generator) -> tuple[str, str]:
    """One reaction as ``(reactants, product)``."""
    scaffold = _random_scaffold(rng, int(rng.integers(8, 22)))
    frag = FRAGMENTS[rng.integers(len(FRAGMENTS))]
    kind = ["addition", "removal", "swap"][rng.integers(3)]
    if kind == "addition":
        lg = LEAVING_GROUPS[rng.integers(len(LEAVING_GROUPS))]
        return f"{scaffold}.{frag}{lg}", f"{scaffold}({frag})"
    if kind == "removal":
        return f"{scaffold}({frag})", scaffold
    lg = LEAVING_GROUPS[rng.integers(len(LEAVING_GROUPS))]
    nuc = FRAGMENTS[rng.integers(len(FRAGMENTS))]
    return f"{scaffold}({lg}).{nuc}", f"{scaffold}({nuc})"


def pairs(n: int, seed: int, direction: str) -> list[tuple[str, str]]:
    """``n`` (source, target) SMILES pairs; the same seed gives the same
    pairs as ``SyntheticReactionDataset(n, seed=seed, direction=...)``."""
    if direction not in ("forward", "retro"):
        raise ValueError(f"direction {direction!r}")
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        reactants, product = make_reaction(rng)
        out.append((reactants, product) if direction == "forward"
                   else (product, reactants))
    return out


def encode_pairs(tok: Tokenizer, prs, src_len: int, tgt_len: int):
    """Token arrays for pairs whose source (+EOS) fits ``src_len`` and
    whose target (+EOS) fits ``tgt_len``: ``src`` (N, src_len) and ``tgt``
    (N, tgt_len), both EOS-terminated and PAD-filled. Longer pairs drop."""
    src = np.zeros((len(prs), src_len), np.int32)
    tgt = np.zeros((len(prs), tgt_len), np.int32)
    n = 0
    for s, t in prs:
        si, ti = tok.encode(s) + [EOS], tok.encode(t) + [EOS]
        if len(si) > src_len or len(ti) > tgt_len:
            continue
        src[n, :len(si)] = si
        tgt[n, :len(ti)] = ti
        n += 1
    return src[:n], tgt[:n]
