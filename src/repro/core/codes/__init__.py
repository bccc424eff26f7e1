"""Pluggable erasure-code families on one shared pipelined data plane.

Public surface::

    from repro.core import codes
    code = codes.make("lrc", 16, 11, l=16, seed=0)   # by family name
    code = codes.from_spec(codes.CodeSpec.from_manifest(manifest))
    codes.families()                                  # registered names

Families register lazily (constructor paths, resolved at first ``make``)
so this package imports without dragging in every family module and stays
cycle-free with ``repro.core.rapidraid``.
"""
from repro.core.codes.base import (CodeSpec, ErasureCode, independent_rows,
                                   matrix_repair_plan)
from repro.core.codes.registry import families, from_spec, make, register

register("rapidraid", "repro.core.rapidraid:_make_canonical")
register("lrc", "repro.core.codes.lrc:make")
register("xorbas", "repro.core.codes.lrc:make_xorbas")
register("mbr", "repro.core.codes.regenerating:make")

__all__ = ["CodeSpec", "ErasureCode", "independent_rows",
           "matrix_repair_plan", "families", "from_spec", "make", "register"]
