"""Per-slot decode-state protocol — ONE discipline for every cache part.

The serving cache is a *slot-state tree*: a dict of ``pos{i}`` layer
entries (leaves stacked over the period axis) plus optional top-level
arrays (``enc_out``).  Every part a mixer can own — pooled ring KV,
cross-attention KV, SSM conv/state, encoder output — is addressed by a
pool slot index and obeys the same three-rule protocol, which is what
lets one fused decode loop and one chunked-prefill executable serve all
arch families (attn-only, SSM, hybrid, enc-dec, VLM) without per-mixer
special cases:

1. **Slot addressing.**  Outside the period scan a layer leaf is
   ``(n_periods, batch, ...)`` — the slot axis is 1; a bare top-level
   array (``enc_out``) carries the slot on axis 0.  *Inside* the period
   scan (``lax.scan`` over the period axis) the slot axis is 0, and
   :func:`take_row` / :func:`put_row` move one slot's row in and out
   with ``slot`` traced, so one executable serves every slot.  The
   decode step and the prefill chunk instead carry the whole
   period-stacked pool through their scans and address one layer with
   :func:`take_layer` / :func:`put_layer`, or one layer's slot row with
   :func:`take_slot` / :func:`put_slot` (``layer`` and ``slot``
   traced), so the pool is updated in place rather than restacked at
   every step or chunk.

2. **Eviction** (:func:`clear_slot`) is uniform: parts with ring
   bookkeeping (a ``slot_pos`` leaf — self-attn KV *and* cross-attn KV)
   mark the slot's ring empty (``slot_pos = -1``; payload bytes stay,
   position masking makes them unreachable), every other part zeroes
   the slot row (SSM conv/state, enc_out — zero IS their empty state).

3. **Decode-step advancement** (:func:`decode_advance`) is driven by a
   single ``active`` predicate: ring KV is masked *at the write site*
   (``cache_write_decode(active=..., layer=...)`` writes one row per
   slot into the pool, not O(capacity)); read-only parts (``cross_kv``,
   ``enc_out`` — written once at admission) pass through untouched;
   every recurrent part (SSM conv/state) row-selects new-vs-old via
   :func:`mask_rows` and is written back at its layer — the one read
   and write its recurrence needs anyway.

Nothing here imports the mixers — attention/ssm/transformer import
*this* module, so the protocol stays the bottom of the model stack.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

# Parts whose writes happen inside the mixer's cache-write primitive
# (already masked by ``active`` there) — decode_advance passes them
# through as-written.
WRITE_SITE_MASKED = ("kv",)

# The engine's device-resident per-slot bookkeeping leaves (one (batch,)
# array each — see ``ServeEngine._init_state``).  Named here, at the
# bottom of the model stack, so the mesh placement rules
# (``repro.distributed.sharding.state_specs``) and the engine agree on
# what the slot-state protocol owns.  The ``spec_*`` leaves exist only
# on speculative engines (``ServeEngine(spec=...)``): a per-slot token
# history ring + n-gram hash table that drive self-speculative drafting
# (``repro.serve.spec``), plus device-side acceptance accounting — the
# history/table rows are 2-D (batch, width) but obey the same replicated
# placement as the scalar bookkeeping.
SLOT_STATE_FIELDS = ("pos", "remaining", "last_token", "active", "seed",
                     "fault_pos", "fault_kind",
                     "spec_hist", "spec_ngram", "spec_accept",
                     "spec_blocks")

# Parts written once at admission and only *read* during decode.
READ_ONLY_IN_DECODE = ("cross_kv", "enc_out")


def mask_rows(mask: Optional[jax.Array], new: jax.Array,
              old: jax.Array) -> jax.Array:
    """Select ``new`` where ``mask`` (leading-dims bool) else ``old``."""
    if mask is None:
        return new
    m = mask.reshape(mask.shape + (1,) * (new.ndim - mask.ndim))
    return jnp.where(m, new, old)


def masked_tree(mask: Optional[jax.Array], new: Any, old: Any) -> Any:
    """:func:`mask_rows` over every leaf of a part tree."""
    if mask is None:
        return new
    return jax.tree.map(lambda n, o: mask_rows(mask, n, o), new, old)


def decode_advance(active: Optional[jax.Array], part: str, new: Any,
                   pool: Any, layer: jax.Array) -> Any:
    """Advance one part of the period-stacked ``pool`` after layer
    ``layer``'s decode step under the protocol (rule 3 above).

    ``new`` is what the mixer produced: for a write-site-masked part the
    already-written pool (it is returned as is), for a recurrent part
    the layer's new value, row-selected against the old one and put
    back at ``layer``.  Read-only parts return ``pool`` untouched.
    ``active``: (b,) bool or None (all rows live)."""
    if part in WRITE_SITE_MASKED:
        return new
    if part in READ_ONLY_IN_DECODE:
        return pool
    return put_layer(
        pool, masked_tree(active, new, take_layer(pool, layer)), layer)


def take_layer(tree: Any, layer: jax.Array) -> Any:
    """Read one layer's part tree out of the period-stacked pool (layer
    axis 0, ``layer`` traced).  The compiler fuses the slice into its
    consumer, so attention reads ``pool[layer]`` where it lies."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0,
                                               keepdims=False), tree)


def put_layer(pool: Any, part: Any, layer: jax.Array) -> Any:
    """Inverse of :func:`take_layer`: write one layer's part tree back
    into the pool in place."""
    return jax.tree.map(
        lambda p, r: jax.lax.dynamic_update_index_in_dim(
            p, r.astype(p.dtype), layer, 0),
        pool, part)


def take_slot(tree: Any, layer: jax.Array, slot: jax.Array) -> Any:
    """Read one layer's row ``slot`` (kept as a size-1 axis) out of the
    period-stacked pool; the compiler fuses the slice into its
    consumer."""
    return take_row(take_layer(tree, layer), slot)


def put_slot(pool: Any, row: Any, layer: jax.Array,
             slot: jax.Array) -> Any:
    """Inverse of :func:`take_slot`: write the size-1 row back at
    ``[layer, slot]`` in place."""
    return jax.tree.map(
        lambda p, r: jax.lax.dynamic_update_slice(
            p, r[None].astype(p.dtype),
            (layer, slot) + (jnp.int32(0),) * (p.ndim - 2)),
        pool, row)


def take_row(tree: Any, slot: jax.Array) -> Any:
    """Slice one slot's row (kept as a size-1 axis) out of every leaf of
    a part tree *inside* the period scan (slot axis 0, ``slot`` traced)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, 0), tree)


def put_row(pool: Any, row: Any, slot: jax.Array) -> Any:
    """Inverse of :func:`take_row`: write the size-1 row back."""
    return jax.tree.map(
        lambda p, r: jax.lax.dynamic_update_slice_in_dim(p, r, slot, 0),
        pool, row)


def clear_slot(cache: dict, slot: jax.Array) -> dict:
    """Evict pool row ``slot`` from the whole slot-state tree (rule 2).

    Runs jitted with ``slot`` traced — one executable serves every slot.
    Ring parts are O(capacity) bookkeeping (slot_pos only); recurrent
    parts are an O(row) zero."""
    out: dict = {}
    for name, entry in cache.items():
        if not isinstance(entry, dict):
            # bare top-level array (enc_out): slot on axis 0
            out[name] = entry.at[slot].set(jnp.zeros_like(entry[0]))
            continue
        e: dict = {}
        for part, tree in entry.items():
            if isinstance(tree, dict) and "slot_pos" in tree:
                # ring part (self- or cross-attn KV): empty = slot_pos -1
                e[part] = dict(
                    tree, slot_pos=tree["slot_pos"].at[:, slot].set(-1))
            else:
                # recurrent part: zero IS the empty state
                e[part] = jax.tree.map(
                    lambda a: a.at[:, slot].set(jnp.zeros_like(a[:, 0])),
                    tree)
        out[name] = e
    return out
