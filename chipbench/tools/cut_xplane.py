"""Cut a recorded trace down to what the benchmark's reader reads, so
that a small real one can be kept with the tests: the device planes'
``XLA Ops`` lines and the host lines that hold ``chipbench_step``
annotations, with only the metadata those events use.  A one-off tool:
it needs the xplane protobuf classes (tensorflow's), the reader does
not.

    python3 chipbench/tools/cut_xplane.py <in.xplane.pb> <out.xplane.pb> [max steps]
"""

import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2


def main() -> None:
    src, dst = sys.argv[1], sys.argv[2]
    max_steps = int(sys.argv[3]) if len(sys.argv) > 3 else 3
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    # the annotated steps to keep: the first `max_steps`
    window = None
    for plane in space.planes:
        names = {k: v.name for k, v in plane.event_metadata.items()}
        for line in plane.lines:
            evs = [e for e in line.events
                   if names.get(e.metadata_id, "").startswith(
                       "chipbench_step")]
            if evs:
                evs.sort(key=lambda e: e.offset_ps)
                evs = evs[:max_steps]
                t0 = line.timestamp_ns * 1000 + evs[0].offset_ps
                t1 = (line.timestamp_ns * 1000 + evs[-1].offset_ps
                      + evs[-1].duration_ps)
                window = (t0, t1)
    assert window is not None, "no chipbench_step annotation in the trace"
    t0, t1 = window
    pad = (t1 - t0) // max_steps  # device ops lag the host by a step or two
    for plane in space.planes:
        names = {k: v.name for k, v in plane.event_metadata.items()}
        is_dev = plane.name.startswith("/device:TPU:")
        keep_lines = []
        for line in plane.lines:
            base = line.timestamp_ns * 1000
            if is_dev and line.name in ("XLA Ops", "XLA Modules"):
                evs = [e for e in line.events
                       if base + e.offset_ps + e.duration_ps > t0 - pad
                       and base + e.offset_ps < t1 + pad]
            elif not is_dev:
                evs = [e for e in line.events
                       if names.get(e.metadata_id, "").startswith(
                           "chipbench_step")
                       and t0 <= base + e.offset_ps < t1]
            else:
                evs = []
            if evs:
                keep_lines.append((line, evs))
        if not keep_lines:
            continue
        new = out.planes.add()
        new.id, new.name = plane.id, plane.name
        used_ev, used_st = set(), set()
        for line, evs in keep_lines:
            nl = new.lines.add()
            nl.id, nl.name = line.id, line.name
            nl.display_name = line.display_name
            nl.timestamp_ns = line.timestamp_ns
            for e in evs:
                ne = nl.events.add()
                ne.CopyFrom(e)
                keep = [s for s in e.stats
                        if plane.stat_metadata[s.metadata_id].name in (
                            "hlo_category", "poll")]
                del ne.stats[:]
                ne.stats.extend(keep)
                used_ev.add(e.metadata_id)
                used_st.update(s.metadata_id for s in keep)
        for k in used_ev:
            m = plane.event_metadata[k]
            nm = new.event_metadata[k]
            nm.id, nm.name = m.id, m.name
            nm.display_name = m.display_name
            for s in m.stats:
                if plane.stat_metadata[s.metadata_id].name == "hlo_category":
                    nm.stats.add().CopyFrom(s)
                    used_st.add(s.metadata_id)
                    if s.WhichOneof("value") == "ref_value":
                        used_st.add(s.ref_value)
        for line, evs in keep_lines:
            for e in evs:
                for s in e.stats:
                    if s.WhichOneof("value") == "ref_value":
                        used_st.add(s.ref_value)
        for k in used_st:
            new.stat_metadata[k].CopyFrom(plane.stat_metadata[k])
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print(f"{dst}: {len(out.SerializeToString())} bytes, "
          f"{[(p.name, [(ln.name, len(ln.events)) for ln in p.lines]) for p in out.planes]}")


if __name__ == "__main__":
    main()
