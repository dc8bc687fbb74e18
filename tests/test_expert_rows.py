"""The expert layer's row moves (`ops/expert_rows.py`: the dispatch into the
experts' buffer and the combine back to the tokens, each the other's
transpose) against the XLA formulation the decoder runs off the chip
(`model/decoder._gather_rows` and a float32 einsum over the k slots), values
and gradients, under the TPU interpreter: its unwritten memory reads NaN, so
a row the kernels should write and do not shows, and a row they should not
read is poisoned here and must not reach a result."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.model import decoder
from alphafold2_tpu.ops import expert_rows

TOKENS, K, EXPERTS, HELD, START, TILE, DIM = 64, 3, 8, 3, 2, 8, 32


def _routing(choice, held=HELD, capacity_factor=None):
    """The decoder's buffer for a (tokens, k) choice of experts (the index
    arithmetic of `decoder.ExpertLayer`): `held` experts from 2 on, groups
    sorted by expert and then by slot, each padded to whole tiles of 8 rows,
    one after another from row 0; by default the dropless bound."""
    tokens, k = choice.shape
    capacity_factor = capacity_factor or EXPERTS / held
    slots = tokens * k
    local = (choice - START).reshape(slots)
    is_held = (local >= 0) & (local < held)
    local = jnp.where(is_held, local, held)
    load = (local[:, None] == jnp.arange(held)[None, :]).sum(0)
    first = jnp.cumsum(load) - load
    slot = jnp.arange(slots, dtype=jnp.int32)
    order = jnp.argsort(local * slots + slot)
    rank = jnp.argsort(order)
    place = rank - jnp.take(jnp.append(first, 0), local)
    cap = math.ceil(capacity_factor * tokens * k * held / EXPERTS)
    rows = (-(-cap // TILE) + held) * TILE
    group = jnp.maximum(-(-load // TILE), 1) * TILE
    start = jnp.cumsum(group) - group
    at = jnp.take(jnp.append(start, rows), local) + place
    fits = is_held & (at < rows)
    row_of_slot = jnp.where(fits, at, rows)
    tile_group = jnp.minimum((jnp.arange(rows // TILE)[:, None] * TILE
                              >= (start + group)[None, :]).sum(1), held - 1)
    live = jnp.minimum((start[-1] + group[-1]) // TILE, rows // TILE)
    row_group = jnp.repeat(tile_group, TILE)
    within = jnp.arange(rows) - jnp.take(start, row_group)
    filled = within < jnp.take(load, row_group)
    slot_of_row = jnp.where(filled, jnp.take(order, jnp.minimum(
        jnp.take(first, row_group) + within, slots - 1)), slots)
    token_of_row = jnp.where(filled, slot_of_row // k, tokens)
    plan = expert_rows.plan_rows(token_of_row, slot_of_row, row_of_slot,
                                 local, start, live, k=k, tile=TILE)
    return plan, dict(rows=rows, live=int(live), fits=fits, load=load,
                      filled=filled, row_of_slot=row_of_slot,
                      slot_of_row=slot_of_row, token_of_row=token_of_row)


def _choice(case):
    """(tokens, k) distinct experts a token, drawn, then steered to the
    case."""
    keys = jax.random.split(jax.random.PRNGKey(7), TOKENS)
    choice = jax.vmap(lambda key: jax.random.choice(
        key, EXPERTS, (K,), replace=False))(keys)
    if case == "empty_expert":
        # expert 3 (held) chosen by nobody: its group is one tile of padding
        choice = jnp.where(choice == 3, 0, choice)
    elif case == "every_slot_held":
        # every slot to a held expert, each token to all three
        choice = jnp.broadcast_to(jnp.arange(START, START + HELD),
                                  choice.shape)
    elif case == "one_live_tile":
        # three slots in all, to the one expert held: one live tile
        choice = jnp.zeros_like(choice).at[:3, 0].set(START)
    elif case == "spanning":
        # token 5 sends a slot to each held expert
        choice = choice.at[5].set(jnp.arange(START, START + HELD))
    return choice


def _reference(u, out, weights, r):
    """The XLA formulation: the buffer by `_gather_rows`, the combine by a
    gather of (tokens, k, d) and a float32 sum over k."""
    tokens, k = weights.shape
    zero = jnp.zeros((1, u.shape[1]), u.dtype)
    pad = lambda idx, fill: jnp.concatenate(
        [idx, jnp.full((1,) + idx.shape[1:], fill, idx.dtype)])
    buf = decoder._gather_rows(jnp.concatenate([u, zero]), r["token_of_row"],
                               pad(r["row_of_slot"].reshape(tokens, k),
                                   r["rows"]))
    back = decoder._gather_rows(jnp.concatenate([out, zero]),
                                r["row_of_slot"].reshape(tokens, k),
                                pad(r["slot_of_row"][:, None], tokens * k))
    w = jnp.where(r["fits"].reshape(tokens, k), weights, 0.0)
    routed = jnp.einsum("tk,tkd->td", w, back.astype(jnp.float32))
    return buf, routed.astype(out.dtype)


def _operands(dtype, r):
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    u = jax.random.normal(keys[0], (TOKENS, DIM)).astype(dtype)
    out = jax.random.normal(keys[1], (r["rows"], DIM)).astype(dtype)
    weights = jax.random.uniform(keys[2], (TOKENS, K))
    g_buf = jax.random.normal(keys[3], (r["rows"], DIM)).astype(dtype)
    g_tok = jax.random.normal(keys[4], (TOKENS, DIM)).astype(dtype)
    return u, out, weights, g_buf, g_tok


def _close(got, want, tol, what):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    assert got.shape == want.shape, what
    assert np.isfinite(got).all(), what
    scale = max(float(np.abs(want).max()), 1e-6)
    assert float(np.abs(got - want).max()) <= tol * scale, what


CASES = ["drawn", "empty_expert", "every_slot_held", "one_live_tile",
         "spanning"]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 1e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_the_row_moves_match_the_gathers(case, dtype, tol):
    """Values and gradients of both moves against the XLA formulation. Rows
    past the live tiles, and every row of the experts' output that holds no
    slot, are NaN here: the kernels read none of them into a result."""
    # every slot held: a buffer with no tile to spare, so the groups fill it;
    # one live tile: one expert held
    plan, r = _routing(
        _choice(case), held=1 if case == "one_live_tile" else HELD,
        capacity_factor=7 / 3 if case == "every_slot_held" else None)
    rows, live_rows = r["rows"], r["live"] * TILE
    u, out, weights, g_buf, g_tok = _operands(dtype, r)
    held_row = (r["slot_of_row"] < TOKENS * K)[:, None]
    poisoned_out = jnp.where(held_row, out, jnp.nan)
    past_live = (jnp.arange(rows) >= live_rows)[:, None]
    poisoned_g_buf = jnp.where(past_live, jnp.nan, g_buf)

    def kernels(u, out, weights):
        return (expert_rows.dispatch_rows(u, plan, interpret=True),
                expert_rows.combine_rows(out, weights, plan, interpret=True))

    (buf, routed), vjp = jax.vjp(kernels, u, poisoned_out, weights)
    (want_buf, want_routed), want_vjp = jax.vjp(
        lambda u, out, w: _reference(u, out, w, r), u,
        jnp.where(held_row, out, 0), weights)
    _close(buf[:live_rows], want_buf[:live_rows], 0, "buffer")
    _close(routed, want_routed, tol, "combine")

    du, d_out, d_w = vjp((poisoned_g_buf, g_tok))
    want_du, want_d_out, want_d_w = want_vjp(
        (jnp.where(past_live, 0, g_buf), g_tok))
    _close(du, want_du, tol, "the dispatch's gradient")
    _close(d_out[:live_rows], want_d_out[:live_rows], tol,
           "the combine's gradient in the buffer")
    _close(d_w, want_d_w, 1e-5, "the weights' gradient")

    if case == "every_slot_held":
        assert int(r["fits"].sum()) == TOKENS * K
        assert r["live"] == rows // TILE        # every tile live
    if case == "one_live_tile":
        assert r["live"] == 1
    if case == "empty_expert":
        assert int(r["load"][1]) == 0
    if case == "spanning":
        assert int((r["row_of_slot"].reshape(TOKENS, K)[5] < rows).sum()) == 3


def test_padding_rows_of_a_live_tile_are_zeros():
    """The grouped matmul's weight gradient sums every row of a live tile:
    the dispatch writes the rows of a live tile that hold no slot as zeros
    (the interpreter's NaN would show an unwritten one), and its scaled form
    (the combine's gradient in the buffer) does the same."""
    plan, r = _routing(_choice("drawn"))
    live_rows = r["live"] * TILE
    u, out, weights, _, g_tok = _operands(jnp.bfloat16, r)
    empty = ~np.asarray(r["filled"])[:live_rows]
    assert empty.any()
    buf = expert_rows.dispatch_rows(u, plan, interpret=True)[:live_rows]
    assert not np.any(np.asarray(buf, np.float32)[empty])
    _, vjp = jax.vjp(lambda o: expert_rows.combine_rows(
        o, weights, plan, interpret=True), out)
    d_out = np.asarray(vjp(g_tok)[0], np.float32)[:live_rows]
    assert np.isfinite(d_out).all() and not np.any(d_out[empty])


def test_the_plan_lists_each_held_slot_once():
    """The combine's lists name every held slot once, in its own token
    tile, and nothing else; a token tile's chunks start on whole chunks."""
    plan, r = _routing(_choice("spanning"))
    token_tile, chunk, chunks, entries = expert_rows.combine_shape(
        TOKENS, K, HELD, TILE)
    slots = np.asarray(plan.chunk_slot).reshape(-1, entries)
    listed = [s + t * token_tile * K for t, tile_slots in enumerate(slots)
              for s in tile_slots if s >= 0]
    held = np.flatnonzero(np.asarray(r["fits"]))
    assert sorted(listed) == sorted(held.tolist())
    tokens = np.asarray(plan.chunk_token).reshape(-1, entries)
    assert ((tokens == slots // K) | (slots < 0)).all()
    assert (np.asarray(plan.chunk_count) <= chunks).all()


def test_only_float32_and_even_bfloat16_rows_are_admitted():
    assert expert_rows.admits(3072, jnp.bfloat16)
    assert expert_rows.admits(33, jnp.float32)
    assert not expert_rows.admits(33, jnp.bfloat16)
    assert not expert_rows.admits(32, jnp.float16)
