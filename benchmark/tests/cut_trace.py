"""Cut a recorded trace down to a test fixture (a builder's tool, not part
of a run; it needs the profiler's protobuf schema, which tensorflow ships):

    python3 -m benchmark.tests.cut_trace <in.xplane.pb> <out.xplane.pb> [ms]

Keeps, of the first device plane: the ``XLA Modules`` events of ONE run of
the decode segment (the one in the middle of the trace) and the ``XLA Ops``
events that start in ``ms`` milliseconds (default 3) from that run's
midpoint; of the host plane: the ``eng.*`` events of that slice. Of an
operation's metadata only the name (cut to 120 characters), ``tf_op`` and
``program_id`` stay. A whole trace is 60-130 MB and is never committed."""

from __future__ import annotations

import sys
from pathlib import Path


def cut(src: Path, dst: Path, ms: float = 3.0) -> dict:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    space.ParseFromString(Path(src).read_bytes())
    out = xplane_pb2.XSpace()
    device = next(p for p in space.planes if p.name == "/device:TPU:0")
    lines = {ln.name: ln for ln in device.lines}
    runs = [ev for ev in lines["XLA Modules"].events
            if device.event_metadata[ev.metadata_id].name.startswith("jit_seg(")]
    run = runs[len(runs) // 2]
    base = lines["XLA Modules"].timestamp_ns
    lo = run.offset_ps + run.duration_ps // 2
    hi = lo + int(ms * 1e9)

    plane = out.planes.add(id=device.id, name=device.name)
    keep_stats = {k for k, v in device.stat_metadata.items()
                  if v.name in ("tf_op", "program_id")}
    for k, v in device.stat_metadata.items():
        plane.stat_metadata[k].CopyFrom(v)

    def keep_meta(mid: int) -> None:
        if mid in plane.event_metadata:
            return
        meta = device.event_metadata[mid]
        new = plane.event_metadata[mid]
        new.id, new.name = meta.id, meta.name[:120]
        for st in meta.stats:
            if st.metadata_id in keep_stats:
                new.stats.add().CopyFrom(st)

    for name, events in (("XLA Modules", [run]),
                         ("XLA Ops", [ev for ev in lines["XLA Ops"].events
                                      if lo <= ev.offset_ps < hi])):
        src_line = lines[name]
        line = plane.lines.add(id=src_line.id, name=name,
                               timestamp_ns=src_line.timestamp_ns)
        for ev in events:
            keep_meta(ev.metadata_id)
            # an event's own stats (offset, duration again) are not kept
            line.events.add(metadata_id=ev.metadata_id,
                            offset_ps=ev.offset_ps,
                            duration_ps=ev.duration_ps)
    n_ops = len(plane.lines[1].events)

    host = next(p for p in space.planes if p.name == "/host:CPU")
    hplane = out.planes.add(id=host.id, name=host.name)
    for k, v in host.stat_metadata.items():
        hplane.stat_metadata[k].CopyFrom(v)
    n_host = 0
    for src_line in host.lines:
        picked = []
        for ev in src_line.events:
            if not host.event_metadata[ev.metadata_id].name.startswith("eng."):
                continue
            t0 = src_line.timestamp_ns * 1000 + ev.offset_ps - base * 1000
            if t0 + ev.duration_ps >= lo and t0 < hi:
                picked.append(ev)
        if not picked:
            continue
        line = hplane.lines.add(id=src_line.id, name=src_line.name,
                                timestamp_ns=src_line.timestamp_ns)
        for ev in picked:
            hplane.event_metadata[ev.metadata_id].CopyFrom(
                host.event_metadata[ev.metadata_id])
            line.events.add().CopyFrom(ev)
            n_host += 1
    Path(dst).write_bytes(out.SerializeToString())
    return {"bytes": Path(dst).stat().st_size, "ops": n_ops,
            "metadata": len(plane.event_metadata), "host_events": n_host,
            "runs_in_trace": len(runs)}


if __name__ == "__main__":
    print(cut(Path(sys.argv[1]), Path(sys.argv[2]),
              float(sys.argv[3]) if len(sys.argv) > 3 else 3.0))
