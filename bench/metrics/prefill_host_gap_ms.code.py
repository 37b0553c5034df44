"""Device-idle time inside each request's ``serve.prefill`` span, in ms:
the prompt's upload, the dispatches, the cache-write scatters and the
first token's sync that the device waits on, over the prefills."""
from pathlib import Path

from bench import serve_spans


def read(run):
    spans = serve_spans.of(run, Path(__file__).resolve().parents[2])
    return spans.idle_ms("serve.prefill") if spans else None
