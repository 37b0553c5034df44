"""Device-idle time inside each decode step's ``serve.decode`` span, in
ms: page checks, uploads, the dispatch, the argmax sync and the
bookkeeping that the device waits on, over the decode steps launched."""
from pathlib import Path

from bench import serve_spans


def read(run):
    spans = serve_spans.of(run, Path(__file__).resolve().parents[2])
    return spans.idle_ms("serve.decode", lambda st: st["active"]) \
        if spans else None
