"""host_ms.sim: host milliseconds per ``api.simulate`` call of the window
outside the device program: the call's ``api.simulate`` span minus the
``fleet.dispatch`` spans inside it (the program's own spans)."""


def read(ctx):
    spans = ctx["window_spans"]
    calls = [r for r in spans if r.name == "api.simulate"]
    if not calls:
        return None
    disp = [r for r in spans if r.name == "fleet.dispatch"]
    host = []
    for c in calls:
        end = c.start_us + c.dur_us
        inside = sum(d.dur_us for d in disp
                     if c.start_us <= d.start_us and d.start_us + d.dur_us <= end)
        host.append(c.dur_us - inside)
    return sum(host) / len(host) / 1e3
