"""compile_s: seconds of set-up spent lowering and compiling the fleet's
programs (the program's ``fleet.trace_lower`` and ``fleet.compile``
spans); loads from the persistent cache count too."""


def read(ctx):
    spans = [r for r in ctx["setup_spans"]
             if r.name in ("fleet.trace_lower", "fleet.compile")]
    if not spans:
        return None
    return sum(r.dur_us for r in spans) / 1e6
