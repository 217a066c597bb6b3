"""metric-docs clean project: every registration documented, every doc row
emitted (literally, via the f-string family, or via a `<...>` family row)."""


def register(registry):
    registry.counter("train/steps_total", help="documented")
    for k in ("drafted", "accepted"):
        registry.counter(f"serve/{k}_total", help="dynamic family")
    for t in ("acme", "umbrella"):
        registry.gauge(f"serve/pages_tenant_{t}", help="documented family")


def watch(fn, watchdog, tracer):
    with tracer.span("train/step"):
        # the name of a span handed on as a keyword is an emitter too: the
        # index row below would be an orphan without it
        return watchdog(fn, span="train/dispatch")
