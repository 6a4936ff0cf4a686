"""Algorithms 1 & 2 in pure array form (struct-of-arrays over a grid).

``repro.core.access_counts`` walks the layer list once per (workload, batch,
capacity, mode) point in Python.  These kernels evaluate the same recurrences
for *every* GLB capacity and batch at once: entity sizes broadcast to a
``[batch, layer, capacity]`` grid, every branch of the pseudocode becomes a
``where`` mask, and the per-layer sum becomes one sequential running sum along
the layer axis (:func:`_cumsum_layers`).

Bit-compatibility with the scalar reference is a design requirement (the
equivalence tests in ``tests/test_dse_equivalence.py`` pin it): every
expression below mirrors the operand order of the scalar implementation, the
branch arms reproduce the exact ``+=`` sequencing, and the layer reduction
uses ``cumsum`` (left-to-right, like ``sum(per_layer, AccessCounts())``)
rather than pairwise ``sum``.

The kernels are ``xp``-parametric: pass ``numpy`` or ``jax.numpy`` (they are
``jax.jit``/``jax.vmap`` compatible — no Python branching on array values).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.access_counts import MemoryParams
from repro.core.workload import GemmLayer, Workload

MB = 1024 * 1024


@dataclasses.dataclass
class CountGrid:
    """Struct-of-arrays ``AccessCounts`` over an arbitrary grid shape.

    Field semantics match ``repro.core.access_counts.AccessCounts``; every
    field holds an array of the same shape (one element per grid point).
    """

    rd_dram: np.ndarray
    wr_dram: np.ndarray
    rd_glb: np.ndarray
    wr_glb: np.ndarray
    rd_dram_w: np.ndarray
    wr_dram_w: np.ndarray

    @property
    def dram_total(self) -> np.ndarray:
        return self.rd_dram + self.wr_dram + self.rd_dram_w + self.wr_dram_w

    @property
    def dram_exposed(self) -> np.ndarray:
        return self.rd_dram + self.wr_dram

    @property
    def dram_hidden(self) -> np.ndarray:
        return self.rd_dram_w + self.wr_dram_w

    @property
    def glb_total(self) -> np.ndarray:
        return self.rd_glb + self.wr_glb

    def stack(self, others: "list[CountGrid]", xp=np) -> "CountGrid":
        """Stack ``[self, *others]`` along a new leading axis."""
        grids = [self, *others]
        return CountGrid(
            *(
                xp.stack([getattr(g, f.name) for g in grids])
                for f in dataclasses.fields(CountGrid)
            )
        )


def entity_size_grid(workload: Workload, batches, d_w: int = 4) -> np.ndarray:
    """Per-(batch, layer) entity sizes: float64 ``[B, L, 3]`` of (I, O, W) MB.

    The batch axis is materialised by evaluating the workload's own
    ``entity_sizes_mb`` per batch value — entity sizes are not uniformly
    linear in batch (``weights_are_activations`` GEMMs scale W, parameter
    GEMMs do not), so the descriptor stays the single source of truth.
    """
    return np.asarray(
        [workload.entity_sizes_mb(int(b), d_w) for b in batches], dtype=np.float64
    )


def _broadcast_sizes(sizes, xp):
    """Split ``[..., L, 3]`` sizes into I/O/W ``[..., L, 1]`` columns."""
    I = sizes[..., 0][..., None]
    O = sizes[..., 1][..., None]
    W = sizes[..., 2][..., None]
    return I, O, W


def _prev_ofmap(O, xp):
    """Previous layer's ofmap per layer; +inf for the first layer so the
    "previous ofmap stayed resident" branch can never fire there."""
    shape = list(O.shape)
    shape[-2] = 1
    inf = xp.full(shape, xp.inf, dtype=O.dtype)
    return xp.concatenate([inf, O[..., :-1, :]], axis=-2)


def _layer_masks(sizes, xp):
    """(first, last) masks shaped ``[L, 1]`` for broadcasting."""
    n_layers = sizes.shape[-2]
    idx = xp.arange(n_layers)[:, None]
    return idx == 0, idx == n_layers - 1


def inference_count_grid(
    sizes, caps_mb, mem: MemoryParams | None = None, xp=np
) -> CountGrid:
    """Algorithm 1 over a grid: sizes ``[..., L, 3]`` x capacities ``[C]``.

    Returns a :class:`CountGrid` with fields shaped ``[..., C]``.
    """
    mem = mem or MemoryParams()
    sizes = xp.asarray(sizes)
    glb = xp.asarray(caps_mb, dtype=sizes.dtype)
    I, O, W = _broadcast_sizes(sizes, xp)
    first, last = _layer_masks(sizes, xp)
    prev_O = _prev_ofmap(O, xp)
    zero = xp.zeros_like(I * glb)

    # --- GLB (Algorithm 1 lines 2, 4, 11); capacity-independent ------------
    rd_glb_l = I / mem.mbpa_glb + zero
    wr_glb_l = xp.where(first, (I + O) / mem.mbpa_glb, O / mem.mbpa_glb) + zero

    # --- DRAM reads (lines 3-9, 12-20) -------------------------------------
    rd_dram_w_l = W / mem.mbpa_dram + zero
    fits = (I + W) <= glb
    load = first | (prev_O > glb)  # layer 1 always loads its ifmap
    rd_dram_l = xp.where(
        load,
        xp.where(
            fits,
            I / mem.mbpa_dram + zero,
            I / mem.mbpa_dram + (I + W - glb) / mem.mbpa_dram,
        ),
        zero,
    )

    # --- DRAM writes (lines 22-30) ------------------------------------------
    wr_dram_l = xp.where(
        last,
        O / mem.mbpa_dram + zero,
        xp.where(O > glb, (O - glb) / mem.mbpa_dram, zero),
    )

    return CountGrid(
        rd_dram=_layer_sum(rd_dram_l, xp),
        wr_dram=_layer_sum(wr_dram_l, xp),
        rd_glb=_layer_sum(rd_glb_l, xp),
        wr_glb=_layer_sum(wr_glb_l, xp),
        rd_dram_w=_layer_sum(rd_dram_w_l, xp),
        wr_dram_w=_layer_sum(zero, xp),  # inference never writes weights back
    )


def training_count_grid(
    sizes, caps_mb, mem: MemoryParams | None = None, xp=np
) -> CountGrid:
    """Algorithm 2 over a grid: sizes ``[..., L, 3]`` x capacities ``[C]``."""
    mem = mem or MemoryParams()
    sizes = xp.asarray(sizes)
    glb = xp.asarray(caps_mb, dtype=sizes.dtype)
    I, O, W = _broadcast_sizes(sizes, xp)
    first, last = _layer_masks(sizes, xp)
    prev_O = _prev_ofmap(O, xp)
    zero = xp.zeros_like(I * glb)

    # Cumulative forward+backward working set of layers 1..i (GI=I etc.).
    layer_f = I + O + W
    cum = _cumsum_layers(layer_f + layer_f, xp)
    resident = cum <= glb

    # --- GLB action counts (lines 9-10); capacity-independent ---------------
    rd_glb_l = (3 * I + O + 5 * W) / mem.mbpa_glb + zero
    wr_glb_l = (2 * I + 2 * O + 3 * W) / mem.mbpa_glb + zero

    # --- forward DRAM reads: like inference when not resident ----------------
    fits = (I + W) <= glb
    load = first | (prev_O > glb)
    fwd_rd = xp.where(
        load,
        xp.where(
            fits,
            I / mem.mbpa_dram + zero,
            I / mem.mbpa_dram + (I + W - glb) / mem.mbpa_dram,
        ),
        zero,
    )

    # --- backward gradient spills (lines 31-37) ------------------------------
    h = mem.prefetch_hidden_frac
    bspill = layer_f > glb  # GI+GO+GW == I+O+W
    spill = layer_f / mem.mbpa_dram
    spill_exposed = xp.where((~resident) & bspill, spill * (1 - h), zero)
    spill_hidden = xp.where((~resident) & bspill, spill * h, zero)

    rd_dram_l = xp.where(resident, xp.where(first, I / mem.mbpa_dram + zero, zero), fwd_rd) + spill_exposed
    wr_dram_l = xp.where(last, O / mem.mbpa_dram + zero, zero) + spill_exposed
    # Scalar ordering: rd_dram_w accumulates the always-streamed weights first
    # (line "weights always stream"), wr_dram_w accumulates the spill first
    # and the weight write-back (line 39) last.
    rd_dram_w_l = W / mem.mbpa_dram + spill_hidden
    wr_dram_w_l = spill_hidden + W / mem.mbpa_dram

    return CountGrid(
        rd_dram=_layer_sum(rd_dram_l, xp),
        wr_dram=_layer_sum(wr_dram_l, xp),
        rd_glb=_layer_sum(rd_glb_l, xp),
        wr_glb=_layer_sum(wr_glb_l, xp),
        rd_dram_w=_layer_sum(rd_dram_w_l, xp),
        wr_dram_w=_layer_sum(wr_dram_w_l, xp),
    )


def count_grid(sizes, caps_mb, mode: str, mem: MemoryParams | None = None, xp=np) -> CountGrid:
    if mode == "inference":
        return inference_count_grid(sizes, caps_mb, mem, xp)
    if mode == "training":
        return training_count_grid(sizes, caps_mb, mem, xp)
    raise ValueError(f"unknown mode {mode!r}")


def _cumsum_layers(x, xp):
    """Left-to-right running sum over the layer axis (-2).

    On jax it is a sequential ``lax.scan`` that adds in ``np.cumsum``'s
    order: ``jnp.cumsum`` in float64 takes minutes to compile for a TPU
    (181 s at 152 layers, compiling for a described v5e); the scan compiles
    in under a second.
    """
    if xp is np:
        return np.cumsum(x, axis=-2)
    import jax

    def step(total, layer):
        total = total + layer
        return total, total

    xs = xp.moveaxis(x, -2, 0)
    _, rest = jax.lax.scan(step, xs[0], xs[1:])
    return xp.moveaxis(xp.concatenate([xs[:1], rest]), 0, -2)


def _layer_sum(per_layer, xp):
    """Left-to-right sum over the layer axis (bit-identical to the scalar
    ``sum(per_layer, AccessCounts())`` fold, unlike pairwise ``xp.sum``)."""
    return _cumsum_layers(per_layer, xp)[..., -1, :]
