"""The histogram kernels' per-tile accumulation (``accumulate_tile``).

Contracts under test:
  * ``split_bf16x3`` splits any float32 exactly into three bfloat16 parts;
  * ``accumulate_tile``, through both kernels that run it
    (``histogram_pallas`` and ``fused_split_pallas``, interpret mode on the
    CPU), sums statistics of wide dynamic range to within float32 summation
    error of a float64 numpy histogram, with inactive rows, a padded last
    example tile, and frontiers wide enough to take several MXU passes;
  * each MXU pass stays one bfloat16 dot at default precision over at most
    ``LHS_ROWS`` rows: a six-pass
    ``Precision.HIGHEST`` f32 dot per statistic computes the same sums, so
    only the kernel body's jaxpr can tell the two apart.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.histogram.fused import fused_split_pallas
from repro.kernels.histogram.histogram import (
    LHS_ROWS,
    histogram_pallas,
    slot_block,
    split_bf16x3,
)

U = 2.0 ** -24            # float32 unit roundoff
N_ROWS = 1_300            # three 512-row tiles, the last one padded
N_FEATURES = 3
N_BINS = 16


def _wide(rng, shape, lo=-20.0, hi=20.0):
    """Float32 of both signs, magnitudes e^lo .. e^hi."""
    return (np.exp(rng.uniform(lo, hi, shape))
            * rng.choice([-1.0, 1.0], shape)).astype(np.float32)


def _split_cases():
    rng = np.random.default_rng(0)
    mags = np.exp(rng.uniform(np.log(1e-30), np.log(1e30), 1 << 16))
    return {
        "wide": (mags * rng.choice([-1.0, 1.0], mags.shape)).astype(
            np.float32),
        "zero_one": np.array([0.0, -0.0, 1.0, -1.0], np.float32),
        "weights_0_1": rng.integers(0, 2, 1 << 10).astype(np.float32),
    }


@pytest.mark.parametrize("case", sorted(_split_cases()))
def test_split_bf16x3_is_exact(case):
    """hi + mid + lo == x for every float32 in the case, and each part is a
    bfloat16 value."""
    x = _split_cases()[case]
    parts = jax.jit(split_bf16x3)(jnp.asarray(x))
    assert all(p.dtype == jnp.bfloat16 for p in parts)
    hi, mid, lo = (np.asarray(p.astype(jnp.float32), np.float64)
                   for p in parts)
    np.testing.assert_array_equal(hi + mid + lo, x.astype(np.float64))


def _frontier(seed, n_stats, n_slots):
    """Codes, statistics and slots of N_ROWS rows: column 0 a wide-range
    gradient, column 1 a positive hessian, the middle ones wide-range, the
    last 0/1 weights (the example count); a fifth of the rows inactive."""
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, N_BINS, (N_ROWS, N_FEATURES)).astype(np.int32)
    stats = _wide(rng, (N_ROWS, n_stats))
    stats[:, 1] = np.exp(rng.uniform(-5, 5, N_ROWS))
    stats[:, -1] = (rng.random(N_ROWS) < 0.9).astype(np.float32)
    slot = rng.integers(0, n_slots, N_ROWS).astype(np.int32)
    slot[rng.random(N_ROWS) < 0.2] = -1
    return codes, stats, slot


def _hist64(codes, stats, slot, n_slots):
    """(W, F, B, S) float64 sums, sums of magnitudes and row counts."""
    act = slot >= 0
    shape = (n_slots, codes.shape[1], N_BINS, stats.shape[1])
    total, size, count = np.zeros(shape), np.zeros(shape), np.zeros(shape[:3])
    x = stats[act].astype(np.float64)
    for f in range(codes.shape[1]):
        idx = (slot[act], f, codes[act, f])
        np.add.at(total, idx, x)
        np.add.at(size, idx, np.abs(x))
        np.add.at(count, idx, 1.0)
    return total, size, count


# 128 slots take two MXU passes at 3 and 4 statistics (the second one
# narrower at 3), one at 2
@pytest.mark.parametrize("n_slots", [8, 32, 128])
@pytest.mark.parametrize("n_stats", [2, 3, 4])
def test_histogram_kernel_matches_float64(n_stats, n_slots):
    """``histogram_pallas`` within recursive float32 summation error of the
    float64 sums: one rounding per example of a bin, three to join a tile's
    parts and one to add the tile. A bfloat16 product (error ~2^-9) or a
    two-way split (~2^-17) is far outside that bound."""
    codes, stats, slot = _frontier(10 * n_stats + n_slots, n_stats, n_slots)
    got = np.asarray(histogram_pallas(codes, stats, slot, n_slots, N_BINS,
                                      interpret=True), np.float64)
    total, size, count = _hist64(codes, stats, slot, n_slots)
    n_tiles = -(-N_ROWS // 512)
    bound = (count + 4 * n_tiles)[..., None] * U * size
    assert (np.abs(got - total) <= bound).all(), \
        np.max(np.abs(got - total) / np.maximum(bound, 1e-300))


def _gh_gains64(total, size, rows, min_examples):
    """Float64 gh gains of every ordered split position, and a bound on
    the float32 error of each: the g and h sums of left, right and parent
    each off by up to ``rows + 8`` roundings of the parent's sum of
    magnitudes (the bins' sums, the cumulative sum, right = parent - left),
    carried through 0.5 g^2 / h to first order, plus four roundings of each
    score."""
    g, h, n, ga = total[..., 0], total[..., 1], total[..., -1], size[..., 0]
    left = [np.cumsum(a, axis=-1) for a in (g, h, n, ga)]
    par = [a[..., -1:] for a in left]
    right = [p - lf for p, lf in zip(par, left)]
    k = (rows + 8)[:, None, None] * U
    eg, eh = k * par[3], k * par[1]

    def score(g, h):
        return 0.5 * g * g / (h + 1e-12)

    def err(a, h):
        return a / h * eg + 0.5 * (a / h) ** 2 * eh + 4 * U * score(a, h)

    with np.errstate(divide="ignore", invalid="ignore"):  # empty sides
        gain = score(left[0], left[1]) + score(right[0], right[1]) \
            - score(par[0], par[1])
        tol = err(left[3], left[1]) + err(right[3], right[1]) \
            + err(par[3], par[1])
    ok = (left[2] >= min_examples) & (right[2] >= min_examples)
    ok[..., -1] = False
    return np.where(ok, gain, -np.inf), np.where(ok, tol, 0.0)


@pytest.mark.parametrize("n_slots", [8, 32, 128])
@pytest.mark.parametrize("n_stats", [2, 3, 4])
def test_fused_kernel_matches_float64(n_stats, n_slots):
    """``fused_split_pallas``'s split per slot gains, in float64, what its
    float32 gain says to within float32 error, and no less than the float64
    best split less both splits' errors."""
    codes, stats, slot = _frontier(10 * n_stats + n_slots + 1, n_stats,
                                   n_slots)
    gain, feat, sbin = map(np.asarray, fused_split_pallas(
        codes, stats, slot, n_slots, N_BINS, kind="gh", min_examples=1,
        interpret=True))
    total, size, _ = _hist64(codes, stats, slot, n_slots)
    rows = np.bincount(slot[slot >= 0], minlength=n_slots)
    g64, tol = _gh_gains64(total, size, rows, min_examples=1)
    for w in range(n_slots):
        assert feat[w] >= 0, w
        best = np.unravel_index(np.argmax(g64[w]), g64[w].shape)
        got = (w, feat[w], sbin[w] - 1)
        assert abs(float(gain[w]) - g64[got]) <= tol[got], \
            (w, gain[w], g64[got], tol[got])
        assert g64[w][best] - g64[got] <= tol[w][best] + tol[got], w


def _kernel_body(fn, *args):
    """The Pallas kernel body's jaxpr inside the program of ``fn``."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["jaxpr"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    assert len(found) == 1, f"{len(found)} pallas_calls"
    return found[0]


def _per_tile_dots(kernel, n_stats, n_slots):
    """The per-tile MXU dots of ``kernel``'s body (the only
    ``dot_general``s outside its once-per-feature branches), checked to be
    bfloat16 passes at default precision that accumulate in float32, with
    at most ``LHS_ROWS`` rows of parts each and the 3·S·W rows in all."""
    run = {"fused_split_pallas": lambda c, s, o: fused_split_pallas(
               c, s, o, n_slots, 256, interpret=True),
           "histogram_pallas": lambda c, s, o: histogram_pallas(
               c, s, o, n_slots, 256, interpret=True)}[kernel]
    body = _kernel_body(run, jnp.zeros((600, 3), jnp.int32),
                        jnp.zeros((600, n_stats), jnp.float32),
                        jnp.zeros((600,), jnp.int32))
    dots = [e for e in body.eqns if e.primitive.name == "dot_general"]
    for dot in dots:
        lhs, rhs = (v.aval for v in dot.invars)
        assert lhs.dtype == rhs.dtype == jnp.bfloat16, (lhs, rhs)
        assert lhs.shape[0] <= max(LHS_ROWS, 24 * n_stats), lhs.shape
        prec = dot.params["precision"]
        assert prec is None or all(p in (None, jax.lax.Precision.DEFAULT)
                                   for p in prec), prec
        assert dot.params["preferred_element_type"] == jnp.float32
    assert sum(d.invars[0].aval.shape[0] for d in dots) \
        == 3 * n_stats * n_slots
    return dots


@pytest.mark.parametrize("kernel", ["fused_split_pallas", "histogram_pallas"])
@pytest.mark.parametrize("n_stats", [2, 4])
def test_per_tile_dot_is_one_bf16_pass(kernel, n_stats):
    """A narrow frontier's example tile runs one MXU dot, over bfloat16
    operands at default precision, accumulating in float32, with the 3·S·W
    part rows as its LHS."""
    assert len(_per_tile_dots(kernel, n_stats, 8)) == 1


@pytest.mark.parametrize("kernel", ["fused_split_pallas", "histogram_pallas"])
@pytest.mark.parametrize("n_stats,n_slots", [(4, 512), (11, 512)])
def test_wide_frontier_runs_one_bf16_pass_per_slot_block(kernel, n_stats,
                                                         n_slots):
    """A frontier of 512 slots (GBT's 4 statistics; a 10-class forest's 11)
    runs one such pass per ``slot_block`` of slots, each LHS within
    ``LHS_ROWS`` rows, so that the pass's VMEM does not grow with S·W."""
    dots = _per_tile_dots(kernel, n_stats, n_slots)
    assert len(dots) == -(-n_slots // slot_block(n_stats, n_slots)) > 1
