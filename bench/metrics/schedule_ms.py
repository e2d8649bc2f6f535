"""Mean duration of an ``engine.schedule`` span in the traced window,
ms: the host time of one scheduling cycle."""

from harness import phases


def read(run):
    p = phases.of(run, __file__)
    spans = p.span_s.get("engine.schedule") if p is not None else None
    return 1e3 * sum(spans) / len(spans) if spans else None
