"""Incremental fixpoint rounds per apply (``StreamResult.rounds``): the
peeling rounds of the batch's cascade, read by ``rounds.trim``'s reader
of the ``rounds`` count."""
from pathlib import Path

from bench import find

read = find.module(Path(__file__).resolve().parents[2], "metrics",
                   "rounds.trim").read
