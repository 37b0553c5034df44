"""Tokens each held expert computes a layer per decode step: the ``held``
stats of the window's ``serve.decode`` spans (assignments to this chip's
experts, summed over the expert layers) over held experts x expert layers
x decode steps. The deployment the configuration stands for would give
each expert the tokens of all its chips' batches."""
from pathlib import Path

from bench import serve_spans


def read(run):
    spans = serve_spans.of(run, Path(__file__).resolve().parents[2])
    if not spans:
        return None
    held = [st["held"] for _, _, st in spans.named("serve.decode")
            if st["active"] and "held" in st]
    if not held:
        return None
    cfg = run.cfg
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return sum(held) / (cfg["n_routed_experts"] * layers * len(held))
