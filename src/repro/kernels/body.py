"""The one in-kernel PPA evaluation body shared by every Pallas kernel.

Hardware mapping of the paper's computation unit (DESIGN.md §3/§5):

  * index generator (s-1 comparators) -> a segment select chosen at trace
    time from the table's static segment count S (:func:`uses_bisection`):

    - :func:`select_coeffs_sweep` for small S: a compare-select sweep over
      the sorted segment-start vector held in SMEM (scalar memory: every
      comparator reads one start and broadcasts it).  Because starts are
      sorted ascending, the running ``where(x >= starts[s], row_s, acc)``
      sweep leaves exactly the last matching row selected — the vectorised
      analogue of the parallel comparator + priority encoder.  It costs
      about (S - 1) * (order + 2) vector ops per element.
    - :func:`select_coeffs_bisect` for large S: the starts and coefficient
      planes laid out as 128-lane tiles in VMEM (:func:`bisect_layout`);
      a coarse compare against each tile's first start picks the element's
      tile, and a branchless bisection of lane gathers inside it finds the
      last start <= x.  Each of its log2(128) steps and each coefficient
      plane costs one lane gather and one select per tile: 40 gathers an
      element at S = 461 (4 tiles), where the sweep takes 1,840 ops.

    Both return the same row as ``searchsorted(starts, x, "right") - 1``
    clipped to [0, S-1], for every int32 ``x``.
  * truncating multipliers / concat adders -> ``core.datapath.horner_body``
    driven by a :class:`~repro.core.datapath.DatapathPlan`; the shift
    constants are compile-time ints baked into the kernel, and the body is
    the *same code object* the numpy golden model and the jnp reference op
    execute, so the three paths cannot drift apart.

Every Pallas kernel in this package (kernels/ppa.py, kernels/softmax_ppa.py,
kernels/fused.py) takes its table operands from :func:`table_operands` and
calls :func:`ppa_eval_block` for its integer datapath stage; nothing in
this package derives a shift amount on its own.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.datapath import DatapathPlan, horner_body

__all__ = ["BISECT_MIN_SEGMENTS", "uses_bisection", "kernel_name",
           "bisect_layout", "table_operands", "select_coeffs_sweep",
           "select_coeffs_bisect", "ppa_eval_block", "resolve_interpret"]

#: lanes of one vector register row: the width of one bisection tile
LANES = 128

#: tables with at least this many segments select by blocked bisection,
#: smaller ones by the comparator sweep: on one TPU v5e the sweep was faster
#: at 14 segments and the bisection at 24 (PERF.md section 6)
BISECT_MIN_SEGMENTS = 20


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Kernels run in Pallas interpret mode only where the platform is the
    CPU; an explicit bool wins (compiling for a described TPU from a CPU
    host passes ``False``)."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def uses_bisection(num_segments: int) -> bool:
    """Whether a table of ``num_segments`` segments selects by bisection."""
    return num_segments >= BISECT_MIN_SEGMENTS


def kernel_name(stem: str, num_segments: int) -> str:
    """The ``pallas_call`` name, which says the select it compiled with, so
    a device trace shows which path ran."""
    return f"{stem}_{'bisect' if uses_bisection(num_segments) else 'sweep'}"


def bisect_layout(starts, coefs):
    """The table as :func:`select_coeffs_bisect` reads it: starts as
    (nb, 128) and the coefficients as (order + 1, nb, 128) int32, with
    nb = ceil(S / 128).  The padding repeats the last segment, so a padded
    lane is selected only where the last segment is, with its coefficients:
    every int32 input keeps its row."""
    pad = (-starts.shape[0]) % LANES
    st = jnp.pad(starts.astype(jnp.int32), (0, pad), mode="edge")
    co = jnp.pad(coefs.astype(jnp.int32), ((0, pad), (0, 0)), mode="edge")
    return st.reshape(-1, LANES), co.T.reshape(co.shape[1], -1, LANES)


def table_operands(starts, coefs):
    """(in_specs, args) of a table's kernel operands, in the layout its
    segment count selects.

    Sweep: the (S,) int32 starts and the (S, order+1) coefficients
    flattened to 1-D, each one whole-array SMEM block (1-D keeps SMEM free
    of a padded minor dimension).  Bisection: :func:`bisect_layout`, each
    one whole-array VMEM block with a constant index map.  The tables are
    concrete where the models call the kernels, so the layout is built
    once, at trace time, and the program holds it as a constant.
    """
    if not uses_bisection(starts.shape[0]):
        spec = pl.BlockSpec(memory_space=pltpu.SMEM)
        return ([spec, spec], (starts.astype(jnp.int32),
                               coefs.astype(jnp.int32).reshape(-1)))
    args = bisect_layout(starts, coefs)
    specs = [pl.BlockSpec(a.shape, lambda *_, n=a.ndim: (0,) * n)
             for a in args]
    return specs, args


def select_coeffs_sweep(x_int, starts_ref, coef_ref, *, num_segments: int,
                        order: int) -> List:
    """Comparator-sweep segment select: returns the ``order + 1`` coefficient
    planes (a_1..a_n, b) selected per element of ``x_int``.

    ``starts_ref`` is the (S,) start vector and ``coef_ref`` the coefficient
    ROM flattened row-major to (S * (order + 1),) — both 1-D SMEM refs in
    the kernels (see :func:`table_operands`), read by scalar index only.
    """
    k = order + 1
    sel = [jnp.full(x_int.shape, coef_ref[c], dtype=jnp.int32)
           for c in range(k)]
    for s in range(1, num_segments):
        ge = x_int >= starts_ref[s]
        for c in range(k):
            sel[c] = jnp.where(ge, coef_ref[s * k + c], sel[c])
    return sel


def select_coeffs_bisect(x_int, starts_ref, coef_ref, *, num_segments: int,
                         order: int) -> List:
    """Blocked-bisection segment select: the same ``order + 1`` coefficient
    planes as :func:`select_coeffs_sweep`.

    ``starts_ref`` (nb, 128) and ``coef_ref`` (order + 1, nb, 128) are the
    VMEM tiles of :func:`bisect_layout`.  Coarse step: ``in_tile[m]`` holds
    where x reaches tile m's first start.  Fine step: from lane 0, each
    bisection step gathers every tile's start at the candidate lane, keeps
    the element's own tile's, and moves to the candidate where x reaches
    it; the coefficients are gathered at the final lane the same way.
    """
    shape = x_int.shape
    nb = starts_ref.shape[0]
    in_tile = [x_int >= starts_ref[m, 0] for m in range(1, nb)]

    def tiles(ref, *lead):        # each (1, 128) table row, against x's rows
        return [jnp.broadcast_to(ref[(*lead, slice(m, m + 1))], shape)
                for m in range(nb)]

    def gather(rows, lane):       # rows[m][lane], m the element's own tile
        out = None
        for m, row in enumerate(rows):
            got = jnp.take_along_axis(row, lane, axis=1,
                                      mode="promise_in_bounds")
            out = got if m == 0 else jnp.where(in_tile[m - 1], got, out)
        return out

    starts = tiles(starts_ref)
    lane = jnp.zeros(shape, jnp.int32)
    # a table within one tile searches only the lanes it fills
    step = min(LANES, 1 << (num_segments - 1).bit_length()) // 2
    while step:
        cand = lane + step
        lane = jnp.where(x_int >= gather(starts, cand), cand, lane)
        step //= 2
    return [gather(tiles(coef_ref, c), lane) for c in range(order + 1)]


def ppa_eval_block(x_int, starts_ref, coef_ref, plan: DatapathPlan, *,
                   num_segments: int):
    """Segment select (chosen by S) + fixed-point Horner chain for one
    tile."""
    select = (select_coeffs_bisect if uses_bisection(num_segments)
              else select_coeffs_sweep)
    sel = select(x_int, starts_ref, coef_ref, num_segments=num_segments,
                 order=plan.order)
    return horner_body(plan, sel, x_int)
