"""Host spans of the serving program, on the profiler's clock.

Each span is a ``jax.profiler.TraceAnnotation``: while a profiler trace
runs (``jax.profiler.start_trace`` or ``jax.profiler.trace``) it lands
in the trace's host plane, beside the device's programs and ops and on
the same clock, with its stats as event stats. While no trace runs,
:func:`span` returns one shared no-op and no stat is computed: a stat
that costs anything is passed as a callable, or added to the entered
span with :func:`note`, and is evaluated only when the span records.
The profiler is the only switch.

The spans nest on the serving thread; those of one request share its
``rid`` stat. Each entry gives the span, where it is opened, its stats,
and what reads it: a reading of the benchmark's
``bench/program_spans.py`` (queue wait, admission, idle inside rounds,
idle time by span, self time), or the benchmark metric it measures
where the work happens.

``serve.submit`` — ``ReplicaRouter.submit``
    ``rid``; ``replica`` it was queued on. Its end starts the request's
    queue wait (``queue_wait_p90_s``).
``serve.stage`` — ``ServeEngine.stage``, inside submit
    ``rid``; ``tokens`` of the prompt; ``issued`` 1 if a host-to-device
    copy of the prompt was started, else 0. Idle time by span.
``serve.round`` — ``ReplicaRouter.step``
    ``queued`` requests and ``active`` slots over all replicas, before
    admission (``queue_depth_mean``). Device idle inside it is
    ``round_idle_ms``.
``serve.admit`` — ``ServeEngine.admit``
    ``rid``; ``slot``; ``prompt_tokens``; ``prefix_hit_tokens`` mapped
    from the prefix index (paged: shared pages × page size; dense: 0);
    ``emitted`` 1, the first token. Its start ends the queue wait; its
    duration is ``admit_p90_ms``; the hit tokens over the prompt tokens
    are ``prefix_hit_share``.
``serve.prefill`` — the prefill call in admit
    ``tokens`` prefilled (``prefill_busy_share``, ``step_mfu``).
``serve.first_token`` — ``ServeEngine._sample_first``
    The blocking readback of the first token. Self time.
``serve.insert`` — ``_insert_prefilled``
    Paged: ``fresh_pages`` allocated, ``shared_pages`` mapped (prefix
    match, allocation, page insert). Self time; pages in use.
``serve.decode`` — ``ServeEngine.step``
    ``emitted`` tokens the round added to streams, set at its end (with
    the admissions' ``emitted``: ``output_tok_s_per_chip``);
    ``retired`` requests.
``serve.pre_dispatch`` — ``PagedServeEngine._pre_dispatch``
    ``pages_allocated``, ``cow_copies`` for the coming chunk. Self time.
``serve.dispatch`` — the decode enqueue in ``_dispatch_raw``
    ``slots`` decoded (``slot_occupancy``); ``chunk``; ``ctx_tokens``,
    the sum of their positions: the rows of ``step_mfu`` and
    ``decode_attn_roofline``.
``serve.readback`` — ``_step_serial``; ``_consume_oldest``
    The blocking token readback (serial rounds after their dispatch,
    pipelined rounds when consumed). Self time.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "serve."


class _Off:
    """The span while no trace runs: enters, exits, records nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


#: the one no-op span, returned whenever no trace is running
OFF = _Off()


def _values(stats: dict) -> dict:
    return {k: v() if callable(v) else v for k, v in stats.items()}


def span(name: str, **stats):
    """A ``serve.<name>`` span with ``stats``, or :data:`OFF`."""
    if not TraceAnnotation.is_enabled():
        return OFF
    return TraceAnnotation(PREFIX + name, **_values(stats))


def note(sp, **stats) -> None:
    """Add ``stats`` to the entered span ``sp``, if it records."""
    if sp is not OFF:
        sp.set_metadata(**_values(stats))
