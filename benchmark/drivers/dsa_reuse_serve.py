"""Driver of the serving cells of a kind whose later layers REUSE the picks
of an earlier one (a learned indexer on some layers only): ``drivers/
dsa_serve.py`` whole and by import — its loop, reference pass and checks —
with one more selection counter read round the same window:

    facts["dsa"][phase]["rows_reused"]

the query rows x layers that attended a carried pick set
(``ServeTelemetry``'s ``dsa_rows_reused``).  ``dsa_serve`` reads its
counters from its module's ``FAMILIES`` at run time; this file hands it the
longer table for the one run and puts the old one back.  A program whose
telemetry lacks a family leaves ``facts["dsa"]`` out, and the readers then
find nothing to read.
"""
from __future__ import annotations

from . import dsa_serve

#: facts["dsa"][phase] key -> the telemetry's counter
FAMILIES = dict(dsa_serve.FAMILIES, rows_reused="dsa_rows_reused")


def run(**kw) -> dict:
    kept = dict(dsa_serve.FAMILIES)
    dsa_serve.FAMILIES.update(FAMILIES)
    try:
        return dsa_serve.run(**kw)
    finally:
        dsa_serve.FAMILIES.clear()
        dsa_serve.FAMILIES.update(kept)
