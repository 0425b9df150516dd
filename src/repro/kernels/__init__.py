"""repro.kernels — Pallas TPU kernels for the PPA activation datapath
(the paper's computation unit), plus the jit'd model-facing ops and the
pure-jnp oracle.

One shared kernel body (kernels/body.py: a segment select chosen by the
table's segment count, comparator sweep for few segments and blocked
bisection for many, + ``core.datapath.horner_body``) feeds every Pallas
kernel; execution paths are selected through the backend registry in
kernels/ops.py.  All backends are bit-identical (tests assert exact
integer equality)."""

from .body import (ppa_eval_block, select_coeffs_bisect,
                   select_coeffs_sweep)
from .fused import ppa_fused_2d, ppa_fused_apply
from .ops import (Backend, TableConsts, available_backends, get_backend,
                  make_ppa_fn, pack_table, ppa_act, ppa_apply, ppa_gate,
                  ppa_gate_act, ppa_softmax, register_backend)
from .ppa import ppa_eval_2d, ppa_eval_table, table_kernel_args
from .ref import horner_int, ppa_eval_ref
from .softmax_ppa import softmax_ppa_2d

__all__ = ["Backend", "TableConsts", "available_backends", "get_backend",
           "horner_int", "make_ppa_fn", "pack_table", "ppa_act", "ppa_apply",
           "ppa_eval_2d", "ppa_eval_block", "ppa_eval_ref", "ppa_eval_table",
           "ppa_fused_2d", "ppa_fused_apply", "ppa_gate", "ppa_gate_act",
           "ppa_softmax", "register_backend", "select_coeffs_bisect",
           "select_coeffs_sweep",
           "softmax_ppa_2d", "table_kernel_args"]
