"""Model programs layer: the share of the traced slice's device time spent
under the scope ``kda_scan``, in percent: the prefills' chunked delta rule
(the convolution over a whole prompt, the gates, a chunk's two matrices of
channel-wise decays, the triangular solve and the scan over chunks), every
Kimi-Delta-Attention layer's, whatever program ran it.

From the same ``.xplane.pb`` the reducer reads: every operation of every
device plane's ``XLA Ops`` line that holds no other, under the program its
run lies in (``benchmark/scopes.py op_names``); numerator the operations
whose innermost scope of the family's is ``kda_scan``, denominator all of
them (an operation the compiler made has no scope and stays in the
denominator). The 2 s slice holds the prefills that fell into it, about one
a second in a cell that ends one request a second, so single runs scatter;
0 where none did. None where the cell's family names no such scope or the
run has no device trace."""

import bisect

from benchmark import scopes, xplane

SCOPE = "kda_scan"


def scope_share(path, family, scope: str) -> float | None:
    """100 x (seconds under ``scope``) / (seconds of all operations) over
    the device planes of the trace at ``path``, None where none ran."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    named = scopes.op_names(path)
    under = total = 0.0
    for plane in data.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        ops, modules = lines.get(xplane.OPS_LINE), \
            lines.get(xplane.MODULES_LINE)
        if ops is None:
            continue
        runs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                      for ev in (modules.events if modules else ())
                      if ev.duration_ns > 0)
        starts = [s for s, _, _ in runs]
        for ev in ops.events:
            if ev.duration_ns <= 0 or xplane.short(ev.name).startswith(
                    xplane.CONTAINERS):
                continue
            total += ev.duration_ns / 1e9
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            program = ""
            if i >= 0 and ev.start_ns < runs[i][1]:
                program = (scopes.PROGRAM_ID.search(runs[i][2])
                           or [None, ""])[1]
            op_name = named.get((program, ev.name)) \
                or named.get(("", ev.name))
            if op_name and scopes.scope_of(op_name, family.SCOPES) == scope:
                under += ev.duration_ns / 1e9
    return 100.0 * under / total if total > 0 else None


def read(ctx):
    if SCOPE not in ctx["family"].SCOPES or not ctx.get("trace"):
        return None
    found = xplane.find_trace(scopes.work_dir() / "trace")
    return scope_share(found, ctx["family"], SCOPE) if found else None
