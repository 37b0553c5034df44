"""Spans and counters of the served path.

A span is a ``jax.profiler.TraceAnnotation``: it is recorded only while a
profiler trace runs, and then lands in the profile's host plane on the
same clock as the device's ops, with its integer stats beside it. With no
trace running a span costs about a microsecond and records nothing, so
there is no switch. Every name starts with ``serve.``; ``NAMES`` lists
them, children under their parent.

The counters (``ServeStats``) are plain integers, always kept, on the
cluster that owns the workers; each worker's engine and scheduler state
count into the same object. The spans carry the same counts as stats, so
a traced window reads its own share of them.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

from jax.profiler import TraceAnnotation

NAMES = {
    "serve.submit": "ServingCluster.submit: length prediction and enqueue"
                    " (req)",
    "serve.heartbeat": "ServingCluster.heartbeat, one control-plane cycle"
                       " (beat)",
    "serve.place": "placement of the queue (placed, left, refused_b..e)",
    "serve.rebalance": "Algorithm 2 re-balance and the error tracker's"
                       " decay (refused_b..e)",
    "serve.handoff": "placed requests handed to their engines (backlog)",
    "serve.refit": "one worker's underrun re-prediction and perf-model"
                   " refit from its traces (worker)",
    "serve.upkeep": "straggler check and retirement of drained workers",
    "serve.step": "PagedEngine.step, one engine iteration",
    "serve.prefill": "one request's prefill (req, tokens, bucket; with"
                     " sparse experts held, held_max)",
    "serve.prefill_program": "the prompt's upload and the prefill"
                             " program's dispatch",
    "serve.write_kv": "the prompt's K/V scattered into its pages",
    "serve.first_token": "the argmax of the prefill logits and its sync",
    "serve.decode": "one decode iteration (active, slots, preempted,"
                    " empty, pages; with sparse experts held, held_max)",
    "serve.pages": "page checks and preemption",
    "serve.launch": "block-table, length and token uploads and the"
                    " decode_step dispatch",
    "serve.sample": "the argmax of the decode logits and its sync",
    "serve.bookkeep": "trace records, token appends, finishes and frees",
}


# ``span(name, **stats)``: the host span ``name`` with integer stats;
# stats known only at its end are added with ``set_metadata`` on the
# entered span
span = TraceAnnotation


@dataclasses.dataclass
class ServeStats:
    """Counts of the served path since the cluster was built."""
    submitted: int = 0          # requests offered (submit, restore)
    placed: int = 0             # placements made by the placement pass
    heartbeats: int = 0
    # placement checks refused, by the first constraint that failed
    refused: Dict[str, int] = dataclasses.field(
        default_factory=lambda: dict.fromkeys("bcde", 0))
    preemptions: int = 0        # requests evicted for want of a page
    prefills: int = 0
    prompt_tokens: int = 0      # prompt tokens prefilled
    decode_steps: int = 0       # decode iterations launched
    tokens_out: int = 0         # tokens emitted (again after a preemption)
    # slots empty in a decode step while the cluster queue held a request
    # after that heartbeat's placement
    empty_slot_steps: int = 0
    # KV pages the decode steps' attention read: per active slot, the
    # pages of its context and the token the step writes
    decode_kv_pages: int = 0
    # sparse experts (prefills and decode steps): top-k assignments routed
    # to any expert; those to the experts this chip holds; and, per layer
    # and step, the busiest held expert's tokens, summed
    expert_assignments: int = 0
    held_assignments: int = 0
    held_expert_max: int = 0
    queue_wait_s: float = 0.0   # submit to first prefill, summed
    queue_waits: int = 0
