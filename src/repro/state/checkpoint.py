"""State-aware checkpointing: canonical EF layout + slot-diff migration.

``repro.checkpoint.io`` stays a generic npz pytree store; this module is
the slot-registry-driven layer the training driver uses:

  * **save** — bucket-keyed EF slots are permuted to the canonical
    (serial) global-element keying before hitting disk, and the meta
    block records ``ef_layout="canonical"`` plus the slot manifest
    fingerprint, so a checkpoint is portable across ``--pipeline``
    settings by construction;
  * **load** — the archive is restored into the registry-built zeros
    template: slots the archive predates are reported BY NAME from the
    slot diff (registry vs archive) and start at their zeros template
    (this replaces the old hand-maintained ``outer_err`` backfill
    special case — any slot a future optimizer declares gets the same
    treatment for free); slots the archive holds and the registry does
    not declare for this run (the hierarchical schedule's cross-pod EF
    slots, resumed on a run that never consumes them) are dropped, with
    a warning if they held anything but zeros; bucket-keyed slots are
    then scattered into the resuming run's bucket partition.
    Checkpoints written by the
    bucket-major era (meta ``n_buckets=k`` without the canonical flag)
    are canonicalised from their recorded ``k`` on the way in.
"""
from __future__ import annotations

import warnings
from typing import Any, Sequence, Tuple

import numpy as np

from repro.checkpoint.io import load_meta, load_pytree, save_pytree
from repro.state.layout import from_canonical, to_canonical
from repro.state.slots import SlotSpec, StateLayout, StateTree

EF_LAYOUT_CANONICAL = "canonical"


def _archive_slot(key: str) -> Tuple[str, str]:
    """(part, name) of a ``(params, state)`` archive key: part ``"1"``
    holds the state, whose leaves are keyed ``.<slot>``."""
    part, leaf = key.split("|")[0], key.split("|")[-1]
    return part, leaf[1:] if leaf.startswith(".") else leaf


def slot_diff(state_template: StateTree, archive_keys: Sequence[str]
              ) -> Tuple[str, ...]:
    """Slots the registry declares that the archive predates."""
    present = {_archive_slot(k)[1] for k in archive_keys}
    return tuple(n for n in state_template if n not in present)


def save_train_state(path: str, params: Any, state: StateTree, step: int,
                     *, slots: Sequence[SlotSpec], ctx: StateLayout,
                     n_buckets: int, block: int,
                     extra_meta: dict = None) -> None:
    """Save ``(params, state)`` with EF slots in the canonical layout."""
    canon = to_canonical(state.map(lambda a: np.asarray(a)), slots, ctx,
                         n_buckets=n_buckets, block=block)
    meta = {"ef_layout": EF_LAYOUT_CANONICAL, "n_buckets": int(n_buckets),
            "block": int(block), **(extra_meta or {})}
    save_pytree(path, (params, canon), step, meta=meta)


def load_train_state(path: str, params_template: Any,
                     state_template: StateTree, *,
                     slots: Sequence[SlotSpec], ctx: StateLayout,
                     n_buckets: int, block: int) -> Tuple[Any, int]:
    """Restore ``(params, state)`` for a run executing ``n_buckets``
    pipeline buckets; returns ``((params, state), step)``."""
    meta = load_meta(path)
    with np.load(path) as data:
        archive_keys = [k for k in data.files if not k.startswith("__")]
        lost = sorted(name for k in archive_keys
                      for part, name in [_archive_slot(k)]
                      if part == "1" and name not in state_template
                      and np.any(data[k]))
    if lost:
        warnings.warn(
            f"checkpoint {path} holds state slots {lost} that this run "
            "does not declare; their non-zero values are dropped")
    missing = slot_diff(state_template, archive_keys)
    if missing:
        # slot-registry-driven backfill: new slots start at their zeros
        # template — name them precisely instead of a generic key dump
        warnings.warn(
            f"checkpoint {path} predates state slots {sorted(missing)}; "
            "they resume from their zeros template (slot registry diff)")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")       # io's generic key warning
        (params, state), step = load_pytree(
            path, (params_template, state_template), backfill=True)
    state = StateTree({k: np.asarray(v) for k, v in state.items()})
    saved_nb = int(meta.get("n_buckets", 1))
    if meta.get("ef_layout") != EF_LAYOUT_CANONICAL and saved_nb > 1:
        # bucket-major era checkpoint: lift to canonical first
        saved_block = int(meta.get("block", block))
        state = to_canonical(state, slots, ctx, n_buckets=saved_nb,
                             block=saved_block)
    state = from_canonical(state, slots, ctx, n_buckets=n_buckets,
                           block=block)
    return (params, state), step
