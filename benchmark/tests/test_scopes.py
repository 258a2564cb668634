"""``lib/scopes.py``: the arithmetic on hand-made lines, the reader against
``xplane.load`` on the recorded trace, and the protobuf decoder on a
hand-made file."""

import os

import pytest

from benchmark.lib import scopes, xplane

from helpers import TESTS

RECORDED = os.path.join(TESTS, "data", "tpu_v5e_two_steps.xplane.pb.gz")


def test_the_scope_is_the_innermost_moolib_name_on_the_path():
    assert scopes.scope_of(
        "jit(step)/jvp(DecoderLM)/block_0/moe/moolib.moe.experts/ragged_dot"
    ) == "moolib.moe.experts"
    # a transform wraps the first name under it
    assert scopes.scope_of(
        "jit(step)/jvp(moolib.loss)/moolib.vtrace/while/body/mul"
    ) == "moolib.vtrace"
    assert scopes.scope_of(
        "jit(step)/transpose(jvp(DecoderLM))/block_3/attn/"
        "moolib.lm.attn_core/pallas_call"
    ) == "moolib.lm.attn_core"
    assert scopes.scope_of("jit(step)/jvp(ImpalaNet)/div:") == scopes.NO_SCOPE
    assert scopes.scope_of(None) == scopes.NO_SCOPE
    # XLA's expansion of ragged_dot drops the path: found by its name
    assert scopes.scope_of(
        "ragged-dot-none", "%ragged-dot-none.71 = bf16[32768,896] custom-call"
    ) == "moolib.moe.experts"
    assert scopes.scope_of("ragged-dot-none", "%fusion.3") == scopes.NO_SCOPE


def test_scope_seconds_is_self_time_inside_the_window():
    ns = 1e9
    rows = [
        # a while of the V-trace scan, 4 s, holding two body operations
        ("%while", "jit(step)/jvp(moolib.loss)/moolib.vtrace/while",
         0 * ns, 4 * ns),
        ("%mul", "jit(step)/jvp(moolib.loss)/moolib.vtrace/while/body/mul",
         1 * ns, 2 * ns),
        ("%add", "jit(step)/jvp(moolib.loss)/while/body/add",
         2 * ns, 2.5 * ns),
        # then an expert product, an unnamed copy, and a gap
        ("%gmm", "jit(step)/jvp(DecoderLM)/block_0/moe/moolib.moe.experts/"
         "ragged_dot", 4 * ns, 7 * ns),
        ("%ragged-dot-none.9 = bf16[8,2304,896] custom-call",
         "ragged-dot-none", 12 * ns, 13 * ns),
        ("%copy", None, 7 * ns, 7.5 * ns),
        ("%late", "jit(step)/moolib.optimizer/mul", 9 * ns, 12 * ns),
    ]
    got = scopes.scope_seconds({"/device:TPU:0": rows})
    assert got == pytest.approx({
        "moolib.vtrace": 3.5,  # 4 s less the 0.5 s of the body's add
        "moolib.loss": 0.5,
        "moolib.moe.experts": 4.0,  # the path's 3 s and the kernel's 1 s
        scopes.NO_SCOPE: 0.5,
        "moolib.optimizer": 3.0,
    })
    # the scopes add up to the chip's busy time
    events = [xplane.Event(r[0], r[2], r[3]) for r in rows]
    assert sum(got.values()) == pytest.approx(xplane.busy_ns(events) / ns)
    # clipped to a window, and averaged over two chips
    got = scopes.scope_seconds(
        {"/device:TPU:0": rows, "/device:TPU:1": rows[3:]},
        window=(5 * ns, 10 * ns),
    )
    assert got == pytest.approx({
        "moolib.moe.experts": 2.0, scopes.NO_SCOPE: 0.5,
        "moolib.optimizer": 1.0,
    })


def test_the_reader_agrees_with_xplane_load_on_the_recorded_trace():
    ours = scopes.load(RECORDED)
    theirs = xplane.load(RECORDED)
    assert sorted(ours) == xplane.device_planes(theirs) == ["/device:TPU:0"]
    rows = ours["/device:TPU:0"]
    events = theirs["/device:TPU:0"][xplane.OPS_LINE]
    assert len(rows) == len(events) == 1348
    for (name, tf_op, start, end), e in zip(rows, events):
        assert name == e.name
        # ProfileData truncates to whole nanoseconds
        assert abs(start - e.start) < 1 and abs(end - e.end) < 2
    paths = [r[1] for r in rows if r[1]]
    assert len(paths) > 600
    assert any(p.startswith("jit(step)/jvp(ImpalaNet)/ConvSequence_0/")
               for p in paths)
    # that program had no moolib scope: all of its time is unnamed
    assert set(scopes.scope_seconds(ours)) == {scopes.NO_SCOPE}


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, payload):
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def test_the_decoder_on_a_hand_made_file(tmp_path):
    """One TPU plane, one ``XLA Ops`` line at 1000 ns with two events of
    one operation whose metadata carries ``tf_op``, and a host plane that
    is passed over."""
    tf_op = "jit(step)/jvp(DecoderLM)/moolib.lm.head/dot_general"
    stat = _field(1, 7) + _field(5, tf_op)  # metadata_id 7 = tf_op
    meta = _field(1, 3) + _field(2, "%fusion.1 = f32[8]") + _field(5, stat)
    line = (_field(2, "XLA Ops") + _field(3, 1000)
            + _field(4, _field(1, 3) + _field(2, 500_000) + _field(3, 2_000_000))
            + _field(4, _field(1, 3) + _field(2, 4_000_000) + _field(3, 300)))
    other = _field(2, "Steps") + _field(4, _field(1, 3) + _field(3, 5))
    plane = (_field(2, "/device:TPU:0") + _field(3, line) + _field(3, other)
             + _field(4, _field(1, 3) + _field(2, meta))
             + _field(5, _field(1, 7) + _field(2, _field(2, "tf_op"))))
    host = _field(2, "/host:CPU") + _field(3, _field(2, "XLA Ops"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, plane) + _field(1, host))
    got = scopes.load(str(path))
    assert got == {"/device:TPU:0": [
        ("%fusion.1 = f32[8]", tf_op, 1500.0, 3500.0),
        ("%fusion.1 = f32[8]", tf_op, 5000.0, 5000.3),
    ]}
    assert scopes.scope_seconds(got) == pytest.approx(
        {"moolib.lm.head": 2000.3e-9}
    )
