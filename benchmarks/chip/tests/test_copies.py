"""The benchmark's own copies agree with what they were copied from or
with a count by hand."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import chem, flops, model, train, traffic


@pytest.mark.parametrize("direction", ["forward", "retro"])
def test_generator_matches_program(direction):
    from repro.data.synthetic import SyntheticReactionDataset

    ds = SyntheticReactionDataset(300, seed=7, direction=direction)
    assert chem.pairs(300, 7, direction) == list(ds.pairs())
    tok = chem.Tokenizer()
    assert tok.itos == ds.tokenizer.itos and tok.vocab_size == 28
    s, t = ds.pair(5)
    assert tok.encode(s) == ds.tokenizer.encode(s)
    assert tok.decode(tok.encode(t) + [chem.EOS, 9]) == t


def test_due_times_are_one_multiset_in_seed_order():
    a = traffic.due_times(8.0, 30.0, 1)
    b = traffic.due_times(8.0, 30.0, 2**40 + 3)
    assert len(a) == len(b) == 240
    ga, gb = np.diff(a), np.diff(b)
    assert not np.allclose(ga, gb)
    # one multiset of gaps: the spans differ by at most one gap
    assert abs(a[-1] - b[-1]) <= max(ga.max(), gb.max())
    assert a[-1] < 30.0 and b[-1] < 30.0
    assert np.all(np.diff(traffic.due_times(8.0, 30.0, 1)) >= 0)
    assert np.array_equal(traffic.due_times(8.0, 30.0, 5),
                          traffic.due_times(8.0, 30.0, 5))


TINY = dict(d_model=64, n_heads=4, d_ff=256, vocab_size=28,
            n_encoder_layers=2, n_decoder_layers=2, direction="forward")


def test_reference_matches_program_logits():
    from repro.configs.mt import tiny_config
    from repro.models import seq2seq as s2s

    params = model.init_params(jax.random.PRNGKey(3), TINY)
    cfg = tiny_config(28, depth=2, d_model=64)
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(
                s2s.init(jax.random.PRNGKey(0), cfg)))
    rng = np.random.default_rng(0)
    src = jnp.asarray(rng.integers(4, 28, (3, 20))).at[:, 15:].set(0)
    tgt = jnp.asarray(rng.integers(4, 28, (3, 12))).at[:, 0].set(chem.BOS)
    with jax.default_matmul_precision("highest"):
        ours = model.forward(params, TINY, src, tgt)
        theirs, _ = s2s.apply(params, cfg, src, tgt)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                               rtol=1e-5, atol=1e-5)


def test_training_lowers_the_loss():
    tcfg = dict(steps=100, chunk=50, batch=16, lr=2e-3, warmup=20,
                corpus=256, src_len=96, tgt_len=96)
    _, losses = train.train(TINY, tcfg, 2**33 + 1)
    assert losses.shape == (100,)
    assert losses[-10:].mean() < 0.8 * losses[:10].mean()


def test_flops_by_hand_at_mt_retro_widths():
    cfg = dict(d_model=256, d_ff=2048, vocab_size=28, n_encoder_layers=6,
               n_decoder_layers=6)
    d, f, V, S, L = 256, 2048, 28, 33, 40
    enc = 6 * (8 * S * d * d + 4 * S * S * d + 4 * S * d * f)
    kv = 6 * 4 * S * d * d
    dec = 0
    for p in range(L):
        dec += 6 * (8 * d * d + 4 * d * d + 4 * S * d + 4 * d * f
                    + 4 * (p + 1) * d) + 2 * d * V
    assert flops.query(cfg, S, [L]) == pytest.approx(enc + kv + dec)
    assert flops.query(cfg, S, [L, L]) == pytest.approx(enc + kv + 2 * dec)


def test_peak_table():
    assert flops.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peak("cpu")
