"""The profiler's trace read as the raw `XSpace` proto, for what
`jax.profiler.ProfileData` does not show: an event's *metadata* stats.
On a TPU every `XLA Ops` event's metadata holds `tf_op`, JAX's `op_name`
path of the operation (`jit(train_step)/transpose(jvp(L03.Block))/attn/
dot_general:`), which is where `jax.named_scope` writes; the program's
scopes are read from there (`readers/trace_scope_ms.py`). Times are on
`ProfileData`'s clock: a line's `timestamp_ns` plus the event's
`offset_ps`, in ns (checked on `recorded/tiny.xplane.pb`), so they
compare with `benchmark.trace.Trace`'s window.

The message classes are tensorflow's `tsl/profiler/protobuf/xplane_pb2`,
which needs `google.protobuf` alone; it is loaded from its file so that
a traced run does not import tensorflow (ten seconds, and a second
runtime beside jax's in the process that holds the chip)."""

from __future__ import annotations

import functools
import importlib.util
import os

from benchmark import hlo_names, trace

METADATA_PLANE = "/host:metadata"


@functools.lru_cache(maxsize=1)
def _pb2():
    found = importlib.util.find_spec("tensorflow")
    if found is None or not found.submodule_search_locations:
        raise ImportError("benchmark.xspace: no tensorflow distribution "
                          "to take xplane_pb2 from")
    path = os.path.join(list(found.submodule_search_locations)[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    spec = importlib.util.spec_from_file_location("_benchmark_xplane_pb2",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _stat_value(stat, stat_names):
    kind = stat.WhichOneof("value")
    if kind == "ref_value":
        return stat_names.get(stat.ref_value, "")
    return getattr(stat, kind) if kind else None


class View:
    """`device_ops`: {plane name: [(start_ns, end_ns, op_name, label,
    lent)]} of the `XLA Ops` lines. `op_name` is the event's `tf_op`;
    where the compiler made the operation and gave it none, the name
    `benchmark.hlo_names` lends it from the program's HLO in the same
    file (`lent` is then true), or "". `host_events`: [(name, start_ns,
    end_ns)] of every event of the `/host:CPU` plane's thread lines."""

    def __init__(self, xspace):
        self.device_ops, self.host_events = {}, []
        protos = {  # program id (unsigned) -> serialized HloProto
            str(mid % (1 << 64)): stat.bytes_value
            for plane in xspace.planes if plane.name == METADATA_PLANE
            for mid, md in plane.event_metadata.items()
            for stat in md.stats if stat.WhichOneof("value") == "bytes_value"}

        @functools.lru_cache(maxsize=None)
        def module(program_id):  # parsed on first need, for this pass only
            proto = protos.get(program_id)
            return None if proto is None else \
                hlo_names.Module(hlo_names.module_text(proto))

        for plane in xspace.planes:
            if trace.DEVICE_PLANE.match(plane.name):
                self.device_ops[plane.name] = self._ops(plane, module)
            elif plane.name == trace.HOST_PLANE:
                for line in plane.lines:
                    base = line.timestamp_ns
                    self.host_events += [
                        (plane.event_metadata[e.metadata_id].name,
                         base + e.offset_ps / 1000.0,
                         base + (e.offset_ps + e.duration_ps) / 1000.0)
                        for e in line.events]

    @staticmethod
    def _ops(plane, module_of):
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        named = {}  # metadata id -> (op_name, label, lent)
        for mid, md in plane.event_metadata.items():
            stats = {stat_names.get(s.metadata_id): s for s in md.stats}
            op_name = "" if "tf_op" not in stats else \
                _stat_value(stats["tf_op"], stat_names) or ""
            lent = False
            if not op_name and "program_id" in stats:
                module = module_of(str(_stat_value(stats["program_id"],
                                                   stat_names)))
                if module is not None:
                    op_name = module.resolve(md.name.partition(" = ")[0])
                    lent = bool(op_name)
            named[mid] = (op_name, trace.op_label(md.name), lent)
        ops = []
        for line in plane.lines:
            if line.name == trace.OPS_LINE:
                base = line.timestamp_ns
                ops += [(base + e.offset_ps / 1000.0,
                         base + (e.offset_ps + e.duration_ps) / 1000.0,
                         *named[e.metadata_id]) for e in line.events]
        return ops

    def host_spans(self, prefixes, t0=None, t1=None):
        """[(name, start_ns, end_ns)] of the host events whose name
        starts with one of `prefixes`, that lie inside [t0, t1]."""
        return [(n, s, e) for n, s, e in self.host_events
                if n.startswith(tuple(prefixes))
                and (t0 is None or s >= t0) and (t1 is None or e <= t1)]


@functools.lru_cache(maxsize=2)
def load(path):
    """The `View` of one `.xplane.pb` file (kept: a run's readers share
    one parse)."""
    xspace = _pb2().XSpace()
    with open(path, "rb") as fh:
        xspace.ParseFromString(fh.read())
    return View(xspace)


def load_dir(trace_dir):
    """The newest trace under `trace_dir`, or None."""
    path = trace.find_xplane(trace_dir)
    return None if path is None else load(path)
