"""The wire-format reader against the traces recorded on the v5e, against
``jax.profiler.ProfileData`` on the same files, and against a made-up XSpace
written here byte by byte."""

import struct
from pathlib import Path

import pytest

from benchmark import xplane_meta as xm

DATA = Path(__file__).parent / "data"
TRACES = ["small_trace_1chip.xplane.pb", "small_trace_4chip.xplane.pb"]


# ---- a protobuf writer of the few shapes an XSpace uses (tests only) -------

def varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def f_varint(num: int, value: int) -> bytes:
    return varint(num << 3) + varint(value)


def f_bytes(num: int, payload) -> bytes:
    payload = payload.encode() if isinstance(payload, str) else payload
    return varint(num << 3 | 2) + varint(len(payload)) + payload


def stat(meta_id: int, **kind) -> bytes:
    (key, value), = kind.items()
    body = f_varint(1, meta_id)
    if key == "double":
        body += varint(2 << 3 | 1) + struct.pack("<d", value)
    else:
        number = {"uint64": 3, "int64": 4, "str": 5, "bytes": 6, "ref": 7}[key]
        body += f_bytes(number, value) if key in ("str", "bytes") else f_varint(number, value)
    return body


def map_entry(key: int, value: bytes) -> bytes:
    return f_varint(1, key) + f_bytes(2, value)


def event_metadata(mid: int, name: str, *stats: bytes) -> bytes:
    body = f_varint(1, mid) + f_bytes(2, name) + b"".join(f_bytes(5, s) for s in stats)
    return f_bytes(4, map_entry(mid, body))


def stat_metadata(sid: int, name: str) -> bytes:
    return f_bytes(5, map_entry(sid, f_varint(1, sid) + f_bytes(2, name)))


def line(name: str, timestamp_ns: int, *events) -> bytes:
    body = f_bytes(2, name) + f_varint(3, timestamp_ns)
    for mid, offset_ps, duration_ps in events:
        body += f_bytes(4, f_varint(1, mid) + f_varint(2, offset_ps) + f_varint(3, duration_ps))
    return f_bytes(3, body)


def plane(name: str, *parts: bytes) -> bytes:
    return f_bytes(1, f_varint(1, 7) + f_bytes(2, name) + b"".join(parts))


STATS = {1: "tf_op", 2: "hlo_category", 3: "flops", 4: "bytes_accessed", 5: "program_id",
         6: "convolution fusion", 7: "source"}


def made_up_space() -> bytes:
    names = b"".join(stat_metadata(i, n) for i, n in STATS.items())
    device = plane(
        "/device:TPU:0", names,
        event_metadata(1, "%fusion.3 = bf16[8]{0} fusion(bf16[8] %a)",
                       stat(1, str="jit(f)/layers/block/mlp/up/dot_general:"),
                       stat(2, ref=6), stat(3, uint64=1000), stat(4, uint64=64),
                       stat(5, uint64=99), stat(7, str="not kept")),
        event_metadata(2, "%while.1 = () while(() %t)", stat(2, str="while"), stat(5, int64=-1)),
        event_metadata(3, "jit_f(99)"),
        line("XLA Ops", 1_000, (2, 0, 9_000_000), (1, 500_000, 2_000_000), (1, 3_000_000, 2_000_000)),
        line("XLA Modules", 1_000, (3, 0, 9_000_000)))
    host = plane("/host:CPU", event_metadata(1, "bm:traced_window"), event_metadata(2, "other"),
                 line("python3", 1_000, (1, 0, 4_000_000), (2, 0, 1)))
    return device + plane("#Chip0 Misc") + host


def test_made_up_space_reads_back():
    data = made_up_space()
    (dev,) = xm.device_planes(data)
    assert dev.name == "/device:TPU:0" and set(dev.lines) == {"XLA Ops", "XLA Modules"}
    assert dev.metadata[1] == {"name": "%fusion.3 = bf16[8]{0} fusion(bf16[8] %a)",
                               "tf_op": "jit(f)/layers/block/mlp/up/dot_general:",
                               "hlo_category": "convolution fusion",      # a ref: the NAME it points to
                               "flops": 1000, "bytes_accessed": 64, "program_id": 99}
    assert dev.metadata[2]["program_id"] == -1 and dev.metadata[2]["hlo_category"] == "while"
    ops = dev.lines["XLA Ops"]
    assert [op.metadata_id for op in ops] == [2, 1, 1]
    assert ops[1].start_s == pytest.approx(1e-6 + 0.5e-6) and ops[1].duration_s == pytest.approx(2e-6)
    assert xm.device_planes(data, all_stats=True)[0].metadata[1]["source"] == "not kept"
    assert xm.host_spans(data, "bm:") == [("bm:traced_window", pytest.approx(1e-6), pytest.approx(5e-6))]
    assert [p.name for p in xm.planes(data)] == ["/device:TPU:0", "#Chip0 Misc", "/host:CPU"]


def test_a_file_that_is_no_xspace_is_refused():
    with pytest.raises((ValueError, IndexError)):
        xm.device_planes(b"\x0b\x0c not a protobuf \xff\xff\xff")


@pytest.mark.parametrize("trace", TRACES)
def test_every_op_event_joins_to_a_metadata_with_a_category(trace):
    planes = xm.device_planes((DATA / trace).read_bytes())
    assert len(planes) == (4 if "4chip" in trace else 1)
    for p in planes:
        assert p.lines["XLA Ops"], p.name
        for op in p.lines["XLA Ops"]:
            meta = p.metadata[op.metadata_id]
            assert meta["hlo_category"] and meta["name"].startswith("%") and meta["program_id"]
        programs = {str(p.metadata[op.metadata_id]["program_id"]) for op in p.lines["XLA Ops"]}
        modules = {p.metadata[ev.metadata_id]["name"] for ev in p.lines["XLA Modules"]}
        # program_id is the fingerprint in the module event's name
        assert programs <= {name.split("(")[1].rstrip(")") for name in modules}


@pytest.mark.parametrize("trace", TRACES)
def test_the_matmul_fusions_carry_their_jax_name_and_flops(trace):
    dev = xm.device_planes((DATA / trace).read_bytes())[0]
    matmuls = [m for m in dev.metadata.values() if m["name"].startswith("%convolution_tanh_fusion")]
    assert len(matmuls) == 4
    for m in matmuls:
        assert m["tf_op"] == "jit(bm_matmuls)/dot_general:"
        assert m["hlo_category"] == "convolution fusion"
        assert m["flops"] == 17188257792          # 2 * 2048^3 and the tanh's 2048^2 x 2
        assert m["bytes_accessed"] == 3 * 2048 * 2048 * 2
    if "4chip" in trace:
        (psum,) = [m for m in dev.metadata.values() if m["name"].startswith("%psum.7")]
        assert psum["hlo_category"] == "all-reduce" and psum["tf_op"].endswith("/psum:")


@pytest.mark.parametrize("trace", TRACES)
def test_events_are_those_profile_data_gives(trace):
    """Same events, same order, same names; times agree to the nanosecond
    ``ProfileData`` rounds to."""
    from jax.profiler import ProfileData

    ours = {p.name: p for p in xm.planes((DATA / trace).read_bytes())}
    for theirs in ProfileData.from_file(str(DATA / trace)).planes:
        if not (theirs.name.startswith("/device:TPU") or theirs.name.startswith("/host:")):
            continue
        mine = ours[theirs.name]
        for their_line in theirs.lines:
            events = list(their_line.events)
            got = mine.lines.get(their_line.name, [])
            if theirs.name.startswith("/host:") and len(events) != len(got):
                continue             # two host threads may share a line's name
            assert len(events) == len(got), (theirs.name, their_line.name)
            for a, b in zip(events, got):
                assert a.name == mine.metadata[b.metadata_id]["name"]
                assert abs(a.start_ns * 1e-9 - b.start_s) < 1.5e-9
                assert abs(a.duration_ns * 1e-9 - b.duration_s) < 1.5e-9
    spans = xm.host_spans((DATA / trace).read_bytes(), "bm:")
    assert [n for n, _, _ in spans].count("bm:traced_window") == 1
    assert {"bm:step_block", "bm:sleep_to_next_arrival"} <= {n for n, _, _ in spans}
