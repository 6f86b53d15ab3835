"""The causal flash kernels' generalisations — key/value heads that a group
of query heads shares, a window walked as a band of block pairs, a score of
one part, a backward kernel whose key/value accumulators span the sequence or
a share of it, a masked block pair computed as row strips over the sub-tiles
that hold a visible entry — in interpret mode against the einsum form, forward
and backward; and the static count of what the kernels compute."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jumbo_mae_tpu_tpu.ops import attention as attention_ops
from jumbo_mae_tpu_tpu.ops.attention import causal_attention, xla_causal_attention
from jumbo_mae_tpu_tpu.ops.pallas import attention as pallas_attention
from jumbo_mae_tpu_tpu.ops.pallas.attention import (
    CAUSAL_BLOCK,
    HEAD_FIRST,
    ROW_FIRST,
    ROW_LAST,
    SPAN_FIRST,
    SPAN_LAST,
    _backward_walk,
    _causal_span,
    _cuts,
    _lower_triangle,
    _reach,
    _strips,
    _sub_tile,
    causal_block,
    causal_pairs,
    pallas_causal_attention,
)


def _inputs(seed, b, h, g, s, two_part, d_a=16, d_b=8, d_v=12):
    ks = jax.random.split(jax.random.key(seed), 6)
    n = lambda k, shape: jax.random.normal(k, shape, jnp.float32)
    scale = (d_a + d_b * two_part) ** -0.5
    q_b = n(ks[1], (b, h, s, d_b)) * scale if two_part else None
    k_b = n(ks[3], (b, s, d_b)) if two_part else None
    return (n(ks[0], (b, h, s, d_a)) * scale, q_b, n(ks[2], (b, g, s, d_a)), k_b,
            n(ks[4], (b, g, s, d_v))), n(ks[5], (b, h, s, d_v))


def _dense(q_a, q_b, k_a, k_b, v, window):
    """Every (query, key) pair, one query head at a time, the two masks as
    comparisons of positions: nothing of the code under test."""
    group = q_a.shape[1] // k_a.shape[1]
    at = jnp.arange(q_a.shape[2])
    visible = at[None, :] <= at[:, None]
    if window is not None:
        visible &= at[:, None] - at[None, :] < window
    heads = []
    for h in range(q_a.shape[1]):
        s = jnp.einsum("bqd,bkd->bqk", q_a[:, h], k_a[:, h // group])
        if q_b is not None:
            s = s + jnp.einsum("bqd,bkd->bqk", q_b[:, h], k_b)
        probs = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        heads.append(jnp.einsum("bqk,bkd->bqd", probs, v[:, h // group]))
    return jnp.stack(heads, axis=1)


def _given(args):
    """Positions of the operands a case has (a one-part score has no ``q_b``, ``k_b``)."""
    return tuple(i for i, a in enumerate(args) if a is not None)


def _output_and_gradients(fn, args, w):
    """``fn(*args)`` and the gradients of ``(fn(*args) * w).sum()`` by the
    operands given, from one jitted program: a form is compiled once a case
    (op by op, the head loop of ``_dense`` compiles a program an operation)."""
    def both(*xs):
        out = fn(*xs)
        return (out * w).sum(), out

    (_, out), grads = jax.jit(jax.value_and_grad(both, argnums=_given(args), has_aux=True))(*args)
    return out, grads


# (query heads, key/value heads, seq, block, window): groups of 1, 3 and 4; a
# window that is no multiple of the block, one smaller than the block (and
# than a block that is clamped to the sequence), one of whole blocks, one the
# sequence never reaches, one of a single token; sequences that are no
# multiple of the block (40, 50 and 70 at 16); and for the backward kernel's
# sequence-long accumulators a key block that several query blocks of each of
# several members add to, with a padded tail (the whole triangle of five
# blocks under groups of 3, and a window that reaches three blocks back)
CASES = [(3, 3, 40, 16, None), (6, 2, 40, 16, 13), (8, 2, 24, 32, 5), (6, 2, 40, 16, 64),
         (3, 3, 40, 16, 1), (8, 2, 48, 16, 32), (6, 2, 50, 16, 33), (6, 2, 70, 16, None),
         (8, 2, 70, 16, 40),
         # one group of 8 over a single key/value head, no window, one score
         # part, operands as projected (no rotation before them): the
         # rope-free grouped-query layer's call at a head slice of 8
         (8, 1, 70, 16, None),
         # a group of 7 over one key/value head under a window of four whole
         # blocks (reach 4, pairs one to three blocks apart wholly inside it,
         # the fourth cut by its edge: 28 over 4 at 4096 of block 1024)
         (7, 1, 100, 16, 64)]

# the score of two parts (latent attention's) under each generalisation once:
# a window inside a block, inside a clamped block, of whole blocks, and one
# that reaches three blocks back over a padded tail (with none of them it is
# ``tests/test_causal_attention.py``'s)
TWO_PART = [(6, 2, 40, 16, 13), (8, 2, 24, 32, 5), (8, 2, 48, 16, 32), (6, 2, 70, 16, 40)]


@pytest.mark.parametrize("h,g,seq,block,window,two_part",
                         [(*c, False) for c in CASES] + [(*c, True) for c in TWO_PART])
def test_grouped_windowed_kernels_match_every_pair_forward_and_backward(h, g, seq, block, window,
                                                                         two_part):
    args, w = _inputs(seq + h, 2, h, g, seq, two_part)
    out, got = _output_and_gradients(
        lambda *xs: pallas_causal_attention(*xs, block, True, window), args, w)
    want, ref = _output_and_gradients(lambda *xs: _dense(*xs, window), args, w)
    assert out.shape == want.shape == (2, h, seq, 12)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    # the CPU's einsum form takes the same three generalisations
    ein_out, ein = _output_and_gradients(lambda *xs: xla_causal_attention(*xs, window), args, w)
    np.testing.assert_allclose(ein_out, want, rtol=2e-5, atol=2e-6)
    for a, e, r in zip(got, ein, ref, strict=True):
        assert a.shape == e.shape == r.shape
        np.testing.assert_allclose(a, r, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(e, r, rtol=2e-4, atol=2e-5)


def test_heads_64_wide_at_a_group_of_four_match_every_pair_forward_and_backward():
    """The short-convolution family's attention layers: 8 query heads over 2
    key/value heads (a group of 4) whose q, k and v are 64 wide — half a lane
    tile — no window, a padded tail; and the backward kernel's span rule
    counts a 64-wide accumulator as the whole tile it takes."""
    h, g, seq, block = 8, 2, 70, 16
    args, w = _inputs(seq + h, 2, h, g, seq, False, d_a=64, d_v=64)
    out, got = _output_and_gradients(
        lambda *xs: pallas_causal_attention(*xs, block, True, None), args, w)
    want, ref = _output_and_gradients(lambda *xs: _dense(*xs, None), args, w)
    assert out.shape == want.shape == (2, h, seq, 64)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    for a, r in zip(got, ref, strict=True):
        np.testing.assert_allclose(a, r, rtol=2e-4, atol=2e-5)
    assert _causal_span(8, 1024, (64, 64), 2) == _causal_span(8, 1024, (128, 128), 2) == 8
    # 28 whole pairs and 8 diagonal ones at 10 of their 16 sub-tiles
    assert causal_pairs(8192) == (33 * 1024 * 1024, 8192 * 8193 // 2)


@pytest.fixture
def fresh_traces():
    """A kernel's call is traced once a shape and its static arguments (a
    ``jit``, inlined): a test that steers a rule the trace reads starts from no
    trace and leaves none behind."""
    clear = lambda: (pallas_attention._causal_fwd.clear_cache(),
                     pallas_attention._causal_bwd.clear_cache())
    clear()
    yield clear
    clear()


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


SPAN_SEQ, SPAN_BLOCK = 70, 16  # five key blocks, the last padded


def _span_case(h, g, window, two_part):
    """The span cases' operands, and the jitted gradients of the kernels' call
    by them (the rule is read when the call is traced)."""
    args, w = _inputs(SPAN_SEQ + h, 2, h, g, SPAN_SEQ, two_part)
    grad = jax.jit(jax.grad(
        lambda *xs: (pallas_causal_attention(*xs, SPAN_BLOCK, True, window) * w).sum(),
        argnums=_given(args)))
    return args, grad


@functools.cache
def _one_span_gradients(h, g, window, two_part):
    """What every span length of a case is compared with: the gradients under
    the rule's own budget, which holds the five key blocks in one span."""
    args, grad = _span_case(h, g, window, two_part)
    return grad(*args)


# key blocks a span of five: one, two (three spans, the last ragged), three (two spans)
@pytest.mark.parametrize("span", [1, 2, 3])
@pytest.mark.parametrize("h,g,window,two_part", [(6, 2, None, True), (6, 2, 40, False),
                                                 (2, 2, 24, True)])
def test_accumulators_that_outgrow_the_budget_split_the_keys_into_spans(monkeypatch, fresh_traces,
                                                                        h, g, window, two_part, span):
    """The shape rule's other path: with a budget the sequence-long
    accumulators do not fit, the same kernel walks the keys a span at a time
    and a query block's gradient is the sum of its spans' shares; every
    gradient equals the unsplit call's to float32 rounding."""
    widths = (16, 12, 8) if two_part else (16, 12)
    assert _causal_span(5, SPAN_BLOCK, widths, 4) == 5  # the rule's own budget: one span
    budget = next(b for b in range(0, 1 << 20, 4096)
                  if _causal_span(5, SPAN_BLOCK, widths, 4, b) == span)
    whole = _one_span_gradients(h, g, window, two_part)  # before the rule is steered
    args, grad = _span_case(h, g, window, two_part)
    monkeypatch.setattr(pallas_attention, "_causal_span",
                        functools.partial(_causal_span, budget=budget))
    jax.clear_caches()  # the rule is read when the call is traced
    fresh_traces()
    split = grad(*args)
    for a, b in zip(split, whole, strict=True):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-6)
    # and it was the other walk: the kernel's first output leads with the spans
    shares = [eqn.outvars[0].aval.shape[0] for eqn in _eqns(jax.make_jaxpr(grad)(*args).jaxpr)
              if eqn.primitive.name == "pallas_call" and eqn.params["name"] == "causal_attention_bwd"]
    assert shares == [-(-5 // span)]


def test_the_span_rule_at_the_shapes_the_repo_trains():
    """8192 tokens at block 1024 (and the window layers' 512) hold their
    accumulators in one span at every recipe's widths; the two-part widths
    take two at 32 768 tokens, and a number of blocks no span length divides
    is cut into spans as equal as it allows."""
    mla, gqa = (128, 128, 64), (128, 128)
    assert _causal_span(8, 1024, mla, 2) == _causal_span(8, 1024, gqa, 2) == 8
    assert _causal_span(16, 512, gqa, 2) == 16
    assert (_causal_span(16, 1024, mla, 2), _causal_span(32, 1024, mla, 2)) == (16, 16)
    assert (_causal_span(17, 1024, mla, 2), _causal_span(24, 1024, gqa, 2)) == (9, 24)
    assert _causal_span(5, 1024, mla, 2, budget=1) == 1  # never under a block


def test_a_windowed_kernel_sees_neither_the_future_nor_beyond_its_window():
    (q, _, k, _, v), _ = _inputs(4, 1, 4, 2, 48, False)
    run = lambda k, v: pallas_causal_attention(q, None, k, None, v, 16, True, 20)
    base = run(k, v)
    # keys 0..9: queries from 29 on no longer see them, queries before do
    moved = run(k.at[:, :, :10].add(5.0), v.at[:, :, :10].add(5.0))
    np.testing.assert_array_equal(base[:, :, 29:], moved[:, :, 29:])
    assert not np.allclose(base[:, :, :29], moved[:, :, :29])
    later = run(k.at[:, :, 30:].add(5.0), v.at[:, :, 30:].add(5.0))
    np.testing.assert_array_equal(base[:, :, :30], later[:, :, :30])


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("n,block,window", [(8, 16, 16), (8, 16, 17), (8, 16, 40), (5, 32, 5),
                                            (6, 8, 1), (4, 16, 1000), (7, 16, 64)])
def test_the_band_holds_each_pair_with_a_visible_entry_once(n, block, window, backward):
    """The forward kernel's tables; and the backward kernel's walk of the
    same pairs, once a member of a group of two a span of three key blocks,
    with the bits that open and close a query block's, a query head's and a
    span's accumulators."""
    reach = _reach(window, block)
    qi, kj = _lower_triangle(n, reach=reach)
    pairs = list(zip(qi.tolist(), kj.tolist()))
    # block pair (i, j) holds a visible entry iff some row r of i and column
    # c of j have 0 <= r - c < window
    seen = [(i, j) for i in range(n) for j in range(n)
            if any(0 <= r - c < window for r in range(i * block, (i + 1) * block)
                   for c in (j * block, (j + 1) * block - 1))
            or (i == j)]
    assert sorted(pairs) == sorted(seen) and len(set(pairs)) == len(pairs)
    assert list(qi) == sorted(qi)  # one visit of each output block
    assert all(kj[t] < kj[t + 1] for t in range(len(pairs) - 1) if qi[t] == qi[t + 1])
    if not backward:
        return
    steps = list(zip(*(column.tolist() for column in _backward_walk(n, reach=reach, group=2,
                                                                    span=3))))
    for member in (0, 1):
        assert sorted((i, j) for i, j, m, _ in steps if m == member) == sorted(seen)
    # the steps of a span are consecutive, within it a query head's, within
    # those a query block's (its key blocks rising), and each bit marks the
    # first or the last step of its run and no other
    of_span, of_head, of_row = (lambda s: s[1] // 3, lambda s: (s[1] // 3, s[2]),
                                lambda s: (s[1] // 3, s[2], s[0]))
    for key, first, last in [(of_row, ROW_FIRST, ROW_LAST), (of_head, HEAD_FIRST, 0),
                             (of_span, SPAN_FIRST, SPAN_LAST)]:
        keys = [key(s) for s in steps]
        starts = [t for t, k in enumerate(keys) if t == 0 or keys[t - 1] != k]
        ends = [t for t, k in enumerate(keys) if t == len(keys) - 1 or keys[t + 1] != k]
        assert len(starts) == len(set(keys))  # one run each
        assert [t for t, s in enumerate(steps) if s[3] & first] == starts
        assert not last or [t for t, s in enumerate(steps) if s[3] & last] == ends
    assert all(a[1] < b[1] for a, b in zip(steps, steps[1:]) if of_row(a) == of_row(b))
    # the key/value accumulators outlive a member: opened by the first, closed by the last
    assert not any(s[3] & SPAN_FIRST for s in steps if s[2] == 1)
    assert not any(s[3] & SPAN_LAST for s in steps if s[2] == 0)


def test_the_pairs_the_tables_visit_against_those_the_mask_keeps():
    """The cell's shapes: a full layer's triangle of 1024-blocks, and a
    512-token window as a band of blocks of 1024, 512 and 256 against the
    whole triangle (ISSUE 33's readings of whole block pairs were 9.3, 3.9,
    2.0, 1.5, 8.3; every pair of a 512-band at block 512 is masked, and by
    the sub-tiles computed — 10 of 16 on a diagonal block, 3 of 4 at halves —
    the readings are 8.5, 1.5, 1.25, 1.25)."""
    needed = 512 * 513 // 2 + (8192 - 512) * 512
    assert causal_pairs(8192) == (33 * 1024 * 1024, 8192 * 8193 // 2)
    # (block, pairs walked, their sub-tiles computed, of a side, visited / needed)
    for block, pairs, tiles, tile, ratio in [(1024, 15, 8 * 9 + 7 * 3, 256, 1.5),
                                             (512, 31, 31 * 10, 128, 1.25),
                                             (256, 93, 31 * 4 + 31 * 3 + 31 * 3, 128, 1.25)]:
        visited, kept = causal_pairs(8192, 512, block)
        assert len(_lower_triangle(8192 // block, reach=_reach(512, block))[0]) == pairs
        assert (visited, kept, _sub_tile(block)) == (tiles * tile * tile, needed, tile)
        assert visited / kept == pytest.approx(ratio, rel=5e-3)
    assert causal_pairs(8192)[0] / needed == pytest.approx(8.52, rel=1e-3)
    assert 8192 * 8193 // 2 / needed == pytest.approx(8.26, rel=1e-3)
    # the block follows from what the code can observe; a window the sequence
    # never reaches is no window
    assert causal_block(8192, None) == causal_block(400, 512) == CAUSAL_BLOCK
    # under strips the whole block wins from a window of half a block on
    # (PERF.md §6, PR 45); a shorter window keeps whole lane tiles of its length
    assert causal_block(8192, 512) == causal_block(8192, 700) == CAUSAL_BLOCK
    assert [causal_block(8192, w) for w in (511, 300, 130, 100)] == [384, 256, 128, 128]
    assert causal_pairs(8192, 512) == causal_pairs(8192, 512, causal_block(8192, 512))
    assert causal_pairs(400, 512) == causal_pairs(400)
    brute = sum(min(i + 1, 37) for i in range(300))
    assert causal_pairs(300, 37, 16)[1] == brute


def test_a_window_of_four_blocks_at_the_longest_row_one_span_holds():
    """16 384 tokens under a 4096-token window (``smallthinker_pretrain_1x16k``):
    the block stays 1024, a query block reaches four key blocks back, three of
    them wholly inside the window, and the band visits 70 block pairs of the
    triangle's 136 for 58.7 M needed entries of 134.2 M; the 16 diagonal pairs
    and the 12 the window's edge cuts are computed at 10 of their 16 sub-tiles,
    59.5 pairs' worth (1.0625 x what the mask keeps, 1.25 x by whole pairs: at
    8192 tokens the same window keeps 75% of a full layer's pairs, here 44%);
    the backward kernel's accumulators fit one span."""
    assert causal_block(16384, 4096) == CAUSAL_BLOCK and _reach(4096, CAUSAL_BLOCK) == 4
    needed = 4096 * 4097 // 2 + (16384 - 4096) * 4096
    assert causal_pairs(16384, 4096) == ((42 * 16 + 28 * 10) * 256 * 256, needed) == (
        62_390_272, 58_722_304)
    assert causal_pairs(16384) == (130 * 1024 * 1024, 16384 * 16385 // 2)
    assert needed / (16384 * 16385 // 2) == pytest.approx(0.4375, abs=1e-4)
    qi, kj = _lower_triangle(16, reach=4)
    assert len(qi) == 70 and max(qi - kj) == 4
    assert _causal_span(16, CAUSAL_BLOCK, (128, 128), 2) == 16


def test_the_dispatcher_hands_the_window_and_the_groups_to_either_form(monkeypatch):
    (q, _, k, _, v), _ = _inputs(6, 1, 4, 2, 24, False)
    want = _dense(q, None, k, None, v, 7)
    np.testing.assert_allclose(causal_attention(q, None, k, None, v, window=7),  # the CPU's form
                               want, rtol=2e-5, atol=2e-6)
    real, calls = pallas_attention.pallas_causal_attention, []
    monkeypatch.setattr(pallas_attention, "pallas_causal_attention",
                        lambda *xs, window=None, diffusion=None: calls.append(window) or real(
                            *xs, 8, True, window))
    # what the rule reads, as a chip would answer for a sequence this short
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attention_ops, "AUTO_FLASH_MIN_SEQ", 1)
    np.testing.assert_allclose(causal_attention(q, None, k, None, v, window=7),
                               want, rtol=2e-5, atol=2e-6)
    assert calls == [7]


# ------------------------------------------------ a masked pair as row strips


def _visible(block, window, apart):
    """(block, block) bools: which entries of the pair ``apart`` blocks below
    the diagonal a query sees, from positions alone."""
    at = np.arange(block)
    back = apart * block + at[:, None] - at[None, :]
    return (back >= 0) & (back < (window or np.inf))


# (block, window): no window; one inside the block, of one block, of whole
# blocks, and cut at two distances (one tile-aligned, one not, one a token past
# a block); the real kernels' blocks with the recipes' windows
PLANS = [(16, None), (16, 13), (16, 5), (16, 16), (16, 32), (16, 40), (16, 37), (16, 17),
         (32, 5), (32, 70), (512, 512), (1024, 4096), (1024, None), (256, 300), (384, 400)]


@pytest.mark.parametrize("strips", [1, 2, 4, 8])
@pytest.mark.parametrize("block,window", PLANS)
def test_the_plan_of_strips_against_every_entry_of_the_mask(block, window, strips):
    """For every distance the band walks: a pair no mask cuts is one strip, the
    block; a cut pair's strips cover every entry the mask keeps, compute no
    sub-tile that is hidden whole, and compare no more than the sub-tiles the
    mask crosses; a strip that sees nothing is left out."""
    tile = block // strips
    reach = _reach(window, block) if window else 3
    cuts = _cuts(block, window)
    assert set(cuts) <= set(range(reach + 1)) and len(cuts) <= 3
    for apart in range(reach + 1):
        visible = _visible(block, window, apart)
        assert visible.any()  # the tables walk no pair that is hidden whole
        assert (apart in cuts) == (not visible.all())
        lo, hi = cuts.get(apart, (1 - block, block))
        at = np.arange(block)
        np.testing.assert_array_equal(
            visible, (at[:, None] - at[None, :] >= lo) & (at[:, None] - at[None, :] < hi))
        plan = _strips(block, tile, lo, hi)
        if apart not in cuts:
            assert plan == ((0, block, 0, block, 0, block),)
            continue
        computed, compared = np.zeros_like(visible), np.zeros_like(visible)
        for strip in plan:
            rows = slice(strip.row, strip.row_end)
            assert strip.row_end - strip.row == tile and strip.row % tile == 0
            assert all(x % tile == 0 for x in strip[2:])
            assert strip.col <= strip.clear <= strip.clear_end <= strip.col_end
            assert not computed[rows].any()  # a strip once
            computed[rows, strip.col:strip.col_end] = True
            compared[rows, strip.col:strip.clear] = True
            compared[rows, strip.clear_end:strip.col_end] = True
        assert not (visible & ~computed).any()
        assert visible[computed & ~compared].all()
        tiles = lambda x: x.reshape(strips, tile, strips, tile).transpose(0, 2, 1, 3).reshape(
            strips, strips, -1)
        np.testing.assert_array_equal(tiles(computed).any(-1), tiles(visible).any(-1))
        np.testing.assert_array_equal(tiles(compared).any(-1),
                                      tiles(visible).any(-1) & ~tiles(visible).all(-1))


def test_the_sub_tile_follows_from_the_block():
    """A quarter of the block where that is whole 128-lane tiles, else half,
    else the block: the blocks ``causal_block`` gives, and the interpreter's."""
    assert [_sub_tile(b) for b in (1024, 512, 768, 256, 896, 384, 128, 64, 16)] == [
        256, 128, 384, 128, 896, 384, 128, 64, 16]
    for seq, window in [(8192, None), (8192, 512), (16384, 4096), (8192, 300), (8192, 130)]:
        block = causal_block(seq, window)
        assert block % _sub_tile(block) == 0 and _sub_tile(block) % 128 == 0


def _forced(monkeypatch, fresh_traces, strips):
    """The kernels at ``strips`` strips a masked pair, whatever the block (the
    rule is read when a call is traced); returns the tiles the kernels' plans
    are then asked for."""
    fresh_traces()
    monkeypatch.setattr(pallas_attention, "_sub_tile", lambda block: block // strips)
    real, tiles = pallas_attention._strips, []
    monkeypatch.setattr(pallas_attention, "_strips",
                        lambda block, tile, *cut: tiles.append(tile) or real(block, tile, *cut))
    return tiles


# (query heads, key/value heads, seq, block, window, two_part, head width,
# strips): one and two score parts; groups of 1, 7, 8 and (64 wide) 4; no
# window, one inside the block (and inside a clamped block), of one block, of
# whole blocks, one cut at two distances over a padded tail; sequences that
# are no multiple of the block
STRIPPED = [(3, 3, 40, 16, None, True, 16, 4), (7, 1, 100, 16, 64, False, 16, 2),
            (8, 1, 70, 16, None, False, 16, 4), (8, 2, 70, 16, None, False, 64, 2),
            (6, 2, 40, 16, 13, True, 16, 4), (6, 2, 40, 16, 13, False, 16, 2),
            (8, 2, 48, 16, 16, False, 16, 4), (8, 2, 48, 16, 16, True, 16, 2),
            (6, 2, 70, 16, 40, True, 16, 4), (6, 2, 70, 16, 37, False, 16, 4),
            (8, 2, 24, 32, 5, True, 16, 4), (6, 2, 50, 16, 33, False, 16, 8)]


def _forward_and_gradients(args, w, block, window):
    """Output, log-sum-exp and the gradients of ``(out * w).sum()`` through the
    custom VJP's two rules: each kernel once."""
    def both(*xs):
        o, residuals = pallas_attention._causal_vjp_fwd(*xs, block, True, window)
        grads = pallas_attention._causal_vjp_bwd(block, True, window, None, residuals, w)
        return o, residuals[6], [g for g in grads if g is not None]
    return jax.jit(both)(*args)


@pytest.mark.parametrize("h,g,seq,block,window,two_part,width,strips", STRIPPED)
def test_strips_of_a_masked_pair_match_the_einsum_form_and_the_one_strip_body(
        monkeypatch, fresh_traces, h, g, seq, block, window, two_part, width, strips):
    """Forward output, log-sum-exp and every gradient of the kernels that
    compute a masked pair as strips: against the einsum form within the
    file's tolerances, and against the one-strip body (the whole pair under
    its mask) to float32 rounding — a hidden entry's probability and ``ds``
    are exact zeros, so leaving it out only reorders a row's sums."""
    args, w = _inputs(seq + h, 1, h, g, seq, two_part, d_a=width, d_v=width if width == 64 else 12)
    assert _sub_tile(block) == block
    one = _forward_and_gradients(args, w, block, window)
    tiles = _forced(monkeypatch, fresh_traces, strips)
    o, lse, grads = _forward_and_gradients(args, w, block, window)
    assert set(tiles) == {block // strips}  # both kernels were built from the plan of strips
    assert causal_pairs(seq, window, block)[0] < len(_lower_triangle(
        -(-seq // block), reach=_reach(window, block) if window and window < seq else None)[0]
    ) * block * block  # which leaves something out
    want, ref = _output_and_gradients(lambda *xs: xla_causal_attention(*xs, window), args, w)
    np.testing.assert_allclose(o, want, rtol=2e-5, atol=2e-6)
    for a, r in zip(grads, ref, strict=True):
        np.testing.assert_allclose(a, r, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(o, one[0], rtol=1e-5, atol=2e-6)
    np.testing.assert_allclose(lse[..., :seq], one[1][..., :seq], rtol=1e-6, atol=2e-6)
    for a, b in zip(grads, one[2], strict=True):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-6)


@pytest.mark.parametrize("h,g,window,two_part", [(6, 2, None, True), (6, 2, 40, False)])
def test_strips_under_more_than_one_span(monkeypatch, fresh_traces, h, g, window, two_part):
    """The strips' key rows of the span-long accumulators: with a budget that
    splits five key blocks into spans of two, the stripped kernels' gradients
    equal the one-span, one-strip call's."""
    seq, block = 70, 16
    args, w = _inputs(seq + h, 1, h, g, seq, two_part)
    widths = (16, 12, 8) if two_part else (16, 12)
    one = _forward_and_gradients(args, w, block, window)
    budget = next(b for b in range(0, 1 << 20, 4096) if _causal_span(5, block, widths, 4, b) == 2)
    monkeypatch.setattr(pallas_attention, "_causal_span",
                        functools.partial(_causal_span, budget=budget))
    _forced(monkeypatch, fresh_traces, 4)
    _, _, grads = _forward_and_gradients(args, w, block, window)
    for a, b in zip(grads, one[2], strict=True):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-6)


# (seq, window, block): the recipes' layers, a padded tail, and the
# interpreter's blocks (one strip: the count is the walk's)
COUNTED = [(8192, None, None), (16384, None, None), (8192, 512, None), (16384, 4096, None),
           (8192, 512, 1024), (8192, 512, 256), (2148, None, None), (2148, 512, None),
           (8192, 300, None), (70, 40, 16), (24, None, 128), (300, 37, 16)]


@pytest.mark.parametrize("seq,window,block", COUNTED)
def test_the_counter_is_the_sum_of_the_plans_sub_tiles(seq, window, block):
    """``visited`` from the masks alone: a pair of the walk that no mask cuts
    whole, a cut pair by its sub-tiles that hold a visible entry; ``needed`` is
    the mask's own count, as before."""
    visited, needed = causal_pairs(seq, window, block)
    s_pad, blk, win, reach = pallas_attention._causal_band(seq, window, block)
    tile = _sub_tile(blk)
    n, per = blk // tile, {}
    for apart in range((reach if reach is not None else s_pad // blk - 1) + 1):
        visible = _visible(blk, win, apart)
        held = visible.reshape(n, tile, n, tile).any(axis=(1, 3)).sum() * tile * tile
        per[apart] = blk * blk if visible.all() else held
    qi, kj = _lower_triangle(s_pad // blk, reach=reach)
    assert visited == sum(per[a] for a in (qi - kj).tolist())
    assert visited <= len(qi) * blk * blk and (tile < blk) == (visited < len(qi) * blk * blk)
    w = min(window or seq, seq)
    assert needed == sum(min(i + 1, w) for i in range(seq)) <= visited
