"""What is particular to one kind of sketch, found by the configuration's
``kind``.

A configuration file names its kind (``"kind": "<name>"``, required).
The harness imports ``chipbench.kinds.<name>`` and uses only what this
docstring lists, so a new kind is one new file here, beside its
configuration (``chipbench/configs/``), its mix (``chipbench/mixes/``)
and its plain reference (``chipbench/references/``). The driving loop,
the windows,
latencies, spans, traces, per-layer readers and the result line are the
harness's and the same for every kind.

A kind module supplies:

``spec(config)``
    The ``api.SketchSpec`` the configuration serves; ``rehearse.py
    --aot`` compiles its ingest for a described v5e.
``tiny(config)``
    The configuration at the CPU rehearsal's size: the key shape kept,
    the sizes cut.
``Served(cell, traffic)``
    The ``serve.SketchService`` of ``spec(cell.config)`` (its ``svc``),
    with the cell's subscriptions made, and:

    * ``warm_up(tick, query, quiet)``: compile the kind's own programs
      before the window, through the driver's ``tick()`` and
      ``query(tenant, items)`` (both logged for the check); ``quiet`` is
      a tenant with the least traffic. It changes no row that traffic
      reaches.
    * ``after_tick(i)``: read what the subscriptions answered at the
      window's tick ``i`` (its index from the window's first tick).
    * ``record(driver, sample, window)``: what the check needs of the
      run, once the window has closed: a ``chipbench.check.Fed`` (the
      driver's own record of what it submitted, and what the program
      fed its ingest) with the kind's answers and final rows of the
      ``sample`` tenants.
``least_bytes(config, blocks)``
    The least bytes the ingest of each ``(keys, weights)`` block has to
    move, for ``ingest_roofline``.
``draw_sample(sizes, admitted, seed)``
    The tenants the check replays, drawn from the seed.
``compare(record, sample, control=False)``
    ``{"numbers": {name: value}, "covered": {...}}``: every number that
    decides ``correct``; with ``control=True`` the same numbers with the
    kind's control in the program's place, which has to fail.
``LIMITS``
    Each compared number's limit (a number is correct at or under it).
``verdict(numbers)``
    ``correct``: every number within its limit.
"""
from __future__ import annotations

import importlib
from types import ModuleType


def load(name: str) -> ModuleType:
    """The kind module ``chipbench.kinds.<name>``."""
    return importlib.import_module(f"{__name__}.{name}")
