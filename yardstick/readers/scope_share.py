"""The share, in percent, of a jitted program's device time that lies in
operations under one of the program's named scopes (``dtg.routed``,
``dtg.short_conv``): summed operation time under the scope over summed
operation time, over the program's runs in the traced window.
``also_named`` lists instruction names that belong to the scope though
their path does not say so: the compiler replaces ``jax.lax.ragged_dot``
by its own grouped-product calls, ``%ragged-dot-none`` and
``%ragged-dot-metadata``, whose ``op_name`` is that name and no path (my
chip run, PR 28), and only the routed layer makes such a call. ``None``
where the program did not run or nothing carries the scope (a program
without the scopes, as before the PR that brought them)."""

from __future__ import annotations

from yardstick import scoped_ops


def read(facts: dict, *, program: str, scope: str,
         also_named: tuple = ()):
    rows = scoped_ops.rows_within(
        facts, scoped_ops.program_runs(facts, program))
    under = sum(r[2] for r in rows
                if scoped_ops.under(r, scope, also_named))
    if not rows or not under:
        return None
    return 100.0 * under / sum(d for _, _, d, _ in rows)
