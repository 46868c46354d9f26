"""The sorted join core's alignment: ``fused._align_sorted`` against
``jnp.searchsorted``.

``_align_sorted`` takes one of two formulations, chosen from the static
bucket sizes: the two binary searches, or one merged sort of build and
probe keys plus an un-permuting sort.  Its contract is bit identity with
``searchsorted(sk, pk, "left")`` and ``searchsorted(sk, pk, "right")``, in
value and dtype, so the join core's outputs cannot depend on the choice.
Each case checks the shape's own choice and both formulations forced, and
the join core against the same core run on the binary searches.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fused
from repro.core.tensor_engine import capacity_bucket

I64_MAX = np.iinfo(np.int64).max
I64_MIN = np.iinfo(np.int64).min


def _keys(rng, case):
    """(build keys, probe keys, real build rows, real probe rows)."""
    if case == "many_to_many":
        return rng.integers(0, 8, 64), rng.integers(0, 10, 256), 64, 256
    if case == "sentinel":
        # the tail of the build bucket is padding; probes equal to the
        # sentinel, and a padded probe tail
        bk = rng.integers(0, 40, 64)
        pk = rng.integers(0, 50, 128)
        pk[::5] = I64_MAX
        return bk, pk, 40, 100
    if case == "negative":
        pool = np.array([I64_MIN, I64_MIN + 1, -(1 << 40), -3, -1, 0, 7,
                         1 << 62])
        return rng.choice(pool, 32), rng.choice(pool, 96), 32, 96
    if case == "no_matches":
        return (rng.integers(0, 1 << 20, 64) * 2,
                rng.integers(0, 1 << 20, 128) * 2 + 1, 64, 128)
    if case == "all_match":
        bk = rng.permutation(1 << 12)[:256]
        return bk, rng.choice(bk, 512), 256, 512
    if case == "one_build_row":
        return np.array([5]), rng.integers(3, 8, 16), 1, 16
    if case == "few_probes":     # P < B
        return rng.integers(0, 1 << 10, 4096), rng.integers(0, 1 << 10, 8), \
            4096, 8
    if case == "many_probes":    # P >> B
        return rng.integers(0, 6, 4), rng.integers(0, 8, 4096), 4, 4096
    raise ValueError(case)


CASES = {  # case -> does its shape choose the merged sort?
    "many_to_many": True, "sentinel": True, "negative": True,
    "no_matches": True, "all_match": True, "one_build_row": True,
    "few_probes": False, "many_probes": True,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_align_sorted_is_searchsorted_bit_for_bit(case, monkeypatch):
    rng = np.random.default_rng(sorted(CASES).index(case))
    bk, pk, n_build, n_probe = (np.asarray(x, np.int64) if i < 2 else x
                                for i, x in enumerate(_keys(rng, case)))
    B, P = len(bk), len(pk)
    assert fused._merge_beats_search(B, P) is CASES[case]

    bk_m = np.where(np.arange(B) < n_build, bk, I64_MAX)
    sk = jnp.asarray(np.sort(bk_m))
    want = (jnp.searchsorted(sk, pk, side="left"),
            jnp.searchsorted(sk, pk, side="right"))

    def join(ratio):
        with monkeypatch.context() as m:
            if ratio is not None:
                m.setattr(fused, "_SORT_WORK_PER_SEARCH_STEP", ratio)
            got = fused._align_sorted(sk, jnp.asarray(pk))
            cap = capacity_bucket(int(jnp.sum(want[1] - want[0])))
            out = fused._join_sorted(jnp.asarray(bk), jnp.asarray(pk),
                                     n_build, n_probe, cap)
        return got, out

    # the reference: the same core on the binary searches
    _, want_out = join(0)
    for ratio in (None, 0, float("inf")):   # shape's choice, each forced
        got, out = join(ratio)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype, ratio
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        # counts and starts as the core derives them from the bounds
        left, right = got
        live = (np.arange(P) < n_probe) & (pk != I64_MAX)
        counts = np.where(live, np.asarray(right - left), 0)
        want_counts = np.where(live, np.asarray(want[1] - want[0]), 0)
        np.testing.assert_array_equal(counts, want_counts)
        np.testing.assert_array_equal(np.cumsum(counts) - counts,
                                      np.cumsum(want_counts) - want_counts)
        # build_idx, probe_idx, valid, total, has_dup
        for g, w in zip(out, want_out):
            assert g.dtype == w.dtype, ratio
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
