"""Drafting and verification: tokens committed to the top candidate over
the decoder calls made for it, over requests finished in the window.
The paper's lever; exactly 1 without drafts."""

from bench import stats


def read(ctx):
    return stats.tokens_per_call(ctx)
