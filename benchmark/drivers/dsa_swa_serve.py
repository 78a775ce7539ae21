"""Driver of the serving cells of a kind whose full layers SELECT the
positions they attend (a learned indexer) and whose window layers attend
latent rows in per-slot rings: ``drivers/dsa_serve.py`` whole and by import
— its loop, reference pass and checks — with one more counter read round
the same window:

    facts["dsa"][phase]["swa_attended"]

the positions the window layers attended, summed over query rows and
window layers (``ServeTelemetry``'s ``swa_attended``).  ``dsa_serve`` reads
its counters from its module's ``FAMILIES`` at run time; this file hands it
the longer table for the one run and puts the old one back, as
``dsa_reuse_serve.py`` does.  A program whose telemetry lacks a family
leaves ``facts["dsa"]`` out, and the readers then find nothing to read.
"""
from __future__ import annotations

from . import dsa_serve

#: facts["dsa"][phase] key -> the telemetry's counter
FAMILIES = dict(dsa_serve.FAMILIES, swa_attended="swa_attended")


def run(**kw) -> dict:
    kept = dict(dsa_serve.FAMILIES)
    dsa_serve.FAMILIES.update(FAMILIES)
    try:
        return dsa_serve.run(**kw)
    finally:
        dsa_serve.FAMILIES.clear()
        dsa_serve.FAMILIES.update(kept)
