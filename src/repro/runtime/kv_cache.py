"""KV-cache slot management (DESIGN.md §6): static-shape caches with
per-request slots and ring-buffer (sliding-window) insertion — the legacy
backend the paged pool (DESIGN.md §7) is the alternative to.

JAX requires static shapes, so instead of vLLM's dynamically allocated pages
we preallocate (L, B_slots, C, kvh, dh) and emulate the block-table
indirection with gathers over slot ids. Sliding-window layers allocate
C = window and wrap via modular slot arithmetic (the ring buffer IS the
window — see layers/attention.attn_decode).

Prefill and decode write the stacked cache in place (``insert_chunk``,
``insert_window``).  The TPU keeps (L, B, C, h, dh) with h outside C, so a
scatter whose update windows span h runs on a transposed copy of the whole
cache; the writes index every axis but head_dim (decode) or write each
row's contiguous span with ``dynamic_update_slice`` (prefill).  A prefill
chunk longer than a ring window is written in segments of at most C, in
order, so later entries overwrite earlier ones.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _stacked(cache) -> bool:
    """Stacked (L, B, ...) cache vs per-layer dict {layer_i: {...}}."""
    return "k" in cache


def insert_chunk(cache, kv_chunk, offsets, slot_ids=None, max_len=None):
    """Insert a prefill chunk's KV into the cache.

    stacked: cache {"k": (L,B,C,h,dh), ...}; kv_chunk (k,v,pos) with L axis.
    unrolled: cache {"layer_i": {"k": (B,C,h,dh), ...}}; kv_chunk
    {"layer_i": (k,v,pos)} without the L axis (ring-buffer windows differ
    per layer, so slots are computed per layer).

    Entry j of a row goes to slot ``(offset + j) % C``; pads (pos < 0,
    bucketing) are dropped and every other entry of the cache is kept.
    Each row's chunk is written in place with ``lax.dynamic_update_slice``
    (``_write_row``), not with a ``[:, rows, slots]`` scatter, which the
    TPU runs on a transposed copy of the whole cache.  A chunk longer than
    a ring window goes in segments of at most C, in order, so later
    entries overwrite earlier ones.  ``max_len``: the longest context a
    row holds (None: unknown); a row's positions pass C, and a chunk wraps
    to the row's first slots, only on a ring shorter than it.
    """
    if not _stacked(cache):
        return {name: _insert_layer(cache[name], kv_chunk[name], offsets,
                                    slot_ids)
                for name in cache}
    _, b_sel, s = kv_chunk[2].shape
    c = cache["k"].shape[2]
    if slot_ids is None:
        slot_ids = jnp.arange(b_sel)
    ring = max_len is None or c < max_len
    for lo in range(0, s, c):
        for i in range(b_sel):
            seg = tuple(x[:, i, lo:lo + c] for x in kv_chunk)
            cache = _write_row(cache, seg, slot_ids[i], offsets[i] + lo,
                               ring)
    return cache


def _write_row(cache, kv_row, slot, offset, ring: bool):
    """Write one row's chunk (k (L,S,h,dh), v, pos (L,S); S <= C) into
    slot ``slot`` of the stacked cache in place: entry j goes to slot
    ``(offset + j) % C``.  Entries that do not wrap fill one S-slot window
    from ``min(a, C - S)`` (a = offset % C; where a padded tail would run
    past C the window starts early and the chunk lands shifted in it); on a
    ring the wrapped entries fill a second window from slot 0."""
    s = kv_row[2].shape[1]
    c = cache["pos"].shape[2]
    a = (offset % c).astype(jnp.int32)
    start = jnp.minimum(a, c - s)
    cache = _merge_window(cache, kv_row, slot, start, start - a)
    if ring:
        cache = _merge_window(cache, kv_row, slot, jnp.zeros_like(a), c - a)
    return cache


def _merge_window(cache, kv_row, slot, start, first):
    """Slots ``start + t`` (t < S) of row ``slot`` take chunk entry
    ``j = t + first`` where 0 <= j < S and its pos >= 0, and keep what
    they held elsewhere: a read of the window, a select, and a
    ``dynamic_update_slice`` of it, which the compiler does in place on the
    donated cache."""
    k, v, pos = kv_row
    n_l, s = pos.shape
    j = jnp.arange(s) + first
    shifted = [jnp.roll(x, -first, axis=1) for x in (k, v, pos)]
    take = (j >= 0) & (j < s) & (shifted[2][0] >= 0)
    slot = slot.astype(jnp.int32)
    out = {}
    for name, new in zip(("k", "v", "pos"), shifted):
        leaf = cache[name]
        idx = (jnp.int32(0), slot, start) + (jnp.int32(0),) * (leaf.ndim - 3)
        old = lax.dynamic_slice(leaf, idx, (n_l, 1, s) + leaf.shape[3:])
        m = take.reshape((1, 1, s) + (1,) * (leaf.ndim - 3))
        out[name] = lax.dynamic_update_slice(
            leaf, jnp.where(m, new[:, None], old), idx)
    return out


def insert_window(cache, kv_window):
    """Write a decode/verify window's KV into the stacked cache, every
    layer in one scatter (the layer scan attends read-only,
    ``layers/attention.attn_window``, and returns only the window).

    kv_window: (k (L,B,S_v,h,dh), v, pos (L,B,S_v)); each entry goes to
    slot ``pos % C`` of its row, and pos -1 (inactive rows, unused draft
    slots) is dropped.  Every axis but head_dim is indexed, so the
    scatter writes (head_dim,) rows into the cache in place whatever its
    device layout: the TPU keeps (L, B, C, h, dh) with h outside C, and a
    scatter whose update windows span h (``[:, rows, slots]``, as
    ``insert_chunk`` has it) is done on a transposed copy of the cache.
    """
    k, v, pos = kv_window
    n_l, b, _ = pos.shape
    h = cache["k"].shape[3]
    c = cache["k"].shape[2]
    slots = jnp.where(pos >= 0, pos % c, c)              # OOB -> dropped
    lidx = jnp.arange(n_l)[:, None, None]
    bidx = jnp.arange(b)[None, :, None]
    hidx = jnp.arange(h)[None, None, None, :]
    at_kv = (lidx[..., None], bidx[..., None], slots[..., None], hidx)
    return {"k": cache["k"].at[at_kv].set(k, mode="drop"),
            "v": cache["v"].at[at_kv].set(v, mode="drop"),
            "pos": cache["pos"].at[lidx, bidx, slots].set(pos, mode="drop")}


def _insert_layer(layer, kv, offsets, slot_ids):
    k, v, pos = kv
    b_sel, s = pos.shape
    c = layer["k"].shape[1]
    if slot_ids is None:
        slot_ids = jnp.arange(b_sel)
    bidx = slot_ids[:, None]
    for lo in range(0, s, c):
        ks, vs, ps = k[:, lo:lo + c], v[:, lo:lo + c], pos[:, lo:lo + c]
        seg = ps.shape[1]
        slots = (offsets[:, None] + lo + jnp.arange(seg)[None, :]) % c
        slots = jnp.where(ps >= 0, slots, c)
        layer = {"k": layer["k"].at[bidx, slots].set(ks, mode="drop"),
                 "v": layer["v"].at[bidx, slots].set(vs, mode="drop"),
                 "pos": layer["pos"].at[bidx, slots].set(ps, mode="drop")}
    return layer


def gather_slots(cache, slot_ids):
    """View of the cache rows for the given slots (same tree structure)."""
    if not _stacked(cache):
        return jax.tree.map(lambda c: c[slot_ids], cache)
    return jax.tree.map(lambda c: c[:, slot_ids], cache)


def scatter_slots(cache, rows, slot_ids):
    """Write per-slot rows back into the full cache."""
    if not _stacked(cache):
        return jax.tree.map(lambda c, r: c.at[slot_ids].set(r), cache, rows)
    return jax.tree.map(lambda c, r: c.at[:, slot_ids].set(r), cache, rows)


def reset_slots(cache, slot_ids):
    """Invalidate slots (release finished requests): pos = -1."""
    if not _stacked(cache):
        return {name: dict(lyr, pos=lyr["pos"].at[slot_ids].set(-1))
                for name, lyr in cache.items()}
    new_p = cache["pos"].at[:, slot_ids].set(-1)
    return dict(cache, pos=new_p)
