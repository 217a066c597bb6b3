"""Sum of the process registry's counters whose name matches ``pattern``
(``compile/.*/first_call_s``: the first call of every watched program, which
is its trace, lowering and compile or load from the cache); nothing where none
matches or none has counted (telemetry off)."""

import re


def reduce(ctx, pattern, scale=1.0):
    from accelerate_tpu.telemetry import Counter, get_registry

    rx = re.compile(pattern)
    total = sum(m.value for name, m in get_registry().items()
                if isinstance(m, Counter) and rx.fullmatch(name))
    return scale * total if total else None
