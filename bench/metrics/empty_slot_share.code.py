"""Share of the decode steps' slots that ran empty while a request waited
unplaced in the cluster's queue, in %: the ``empty`` stats over the
``slots`` stats of the window's ``serve.decode`` spans."""
from pathlib import Path

from bench import serve_spans


def read(run):
    spans = serve_spans.of(run, Path(__file__).resolve().parents[2])
    return spans.empty_slot_share() if spans else None
