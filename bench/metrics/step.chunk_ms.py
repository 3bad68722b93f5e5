"""Model step (``models/dense.py`` through ``launch/executor.py``): mean
milliseconds between the arrivals (``fetched_at``) of the tokens of two
consecutive engine cycles where the later cycle fetched a unified step
that carried prompt rows (``fetched_kind`` ``"unified"``,
``fetched_prefill_tokens`` > 0), over the ``step()`` records of the
traced window. An interval counts only where the earlier cycle also
fetched and dispatched a step: the fetched step was then queued while
its predecessor ran, so no idle wait for arrivals enters it. Where the
device sets the pace this is that step's device time; where the host
does, the host's cycle. ``None`` where no interval qualifies, as with a
program whose records lack these keys. Moves ``itl_p95_ms``: a step
that carries a prefill chunk stalls every stream."""


def read(run):
    gaps = [b["fetched_at"] - a["fetched_at"]
            for a, b in zip(run.steps, run.steps[1:])
            if a.get("kind") is not None
            and a.get("fetched_at") is not None
            and b.get("fetched_at") is not None
            and b.get("fetched_kind") == "unified"
            and b.get("fetched_prefill_tokens", 0) > 0]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
