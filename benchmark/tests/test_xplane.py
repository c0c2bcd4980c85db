"""The trace reducer on a small synthetic xplane: busy union, idle share,
gap labelling, convolution time and step count."""

import xplane


def _varint(value):
    out = bytearray()
    while True:
        byte = value & 0x7F
        value >>= 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def _field(number, payload):
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _plane(name, lines, events, stat_names=(), plane_stats=()):
    """lines: [(name, [(metadata id, start ps, duration ps)])];
    events: {id: (name, {stat id: text})}."""
    buf = _field(2, name)
    for line_name, evs in lines:
        body = _field(2, line_name) + _field(3, 0)
        for ident, start, duration in evs:
            body += _field(4, _field(1, ident) + _field(2, start) + _field(3, duration))
        buf += _field(3, body)
    for ident, (ev_name, stats) in events.items():
        meta = _field(1, ident) + _field(2, ev_name)
        for stat_id, text in stats.items():
            meta += _field(5, _field(1, stat_id) + _field(5, text))
        buf += _field(4, _field(1, ident) + _field(2, meta))
    for stat_id, stat_name in stat_names:
        buf += _field(5, _field(1, stat_id) + _field(2, _field(1, stat_id) + _field(2, stat_name)))
    for stat_id, value in plane_stats:
        buf += _field(6, _field(1, stat_id) + _field(3, value))
    return buf


US = 1_000_000  # picoseconds


EPOCH_NS = 1_790_691_882_478_709_749


def _space(host_tracer=True):
    device = _plane(
        "/device:TPU:0",
        [
            ("XLA Ops", [
                (1, 100 * US, 200 * US),   # conv   100..300
                (2, 250 * US, 100 * US),   # fusion 250..350 (overlaps)
                (1, 600 * US, 100 * US),   # conv   600..700
                (2, 900 * US, 50 * US),    # fusion 900..950
            ]),
            ("XLA Modules", [(3, 100 * US, 250 * US), (3, 600 * US, 350 * US)]),
            ("Async XLA Ops", [(2, 0, 1000 * US)]),  # never billed
        ],
        {
            1: ("%convolution.1", {7: "convolution", 8: "jit(train_step)/net/conv2/Conv_0/conv_general_dilated"}),
            2: ("%fusion.2", {7: "loop fusion", 8: "jit(train_step)/transpose(jvp(net))/bn/mul"}),
            3: ("jit_train_step(1)", {}),
        },
        stat_names=[(7, "hlo_category"), (8, "tf_op")],
    )
    host = _plane(
        "/host:CPU",
        [("python", [
            (1, 0, 1000 * US),             # bench.trace_window 0..1000
            (2, 360 * US, 230 * US),       # bench.host_input.next covers gap 350..600
            (3, 700 * US, 190 * US),       # bench.dispatch covers gap 700..900
        ])],
        {1: ("bench.trace_window", {}), 2: ("bench.host_input.next", {}),
         3: ("bench.dispatch", {})},
    )
    task = _plane(
        "Task Environment", [], {}, stat_names=[(1, "profile_start_time")],
        plane_stats=[(1, EPOCH_NS)],
    )
    if not host_tracer:
        return _field(1, device) + _field(1, task)
    return _field(1, device) + _field(1, host) + _field(1, task)


def _epoch_spans():
    """The host plane's spans as a window records them itself."""
    us = 1000  # nanoseconds
    return [
        ("bench.trace_window", EPOCH_NS, EPOCH_NS + 1000 * us),
        ("bench.host_input.next", EPOCH_NS + 360 * us, EPOCH_NS + 590 * us),
        ("bench.dispatch", EPOCH_NS + 700 * us, EPOCH_NS + 890 * us),
    ]


import pytest


@pytest.mark.parametrize("host_tracer", [True, False])
def test_reducer(tmp_path, host_tracer):
    """Host spans from the trace itself, or the window's own on the epoch
    clock with the host tracer off: the same reduction."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_space(host_tracer))
    summary = xplane.summarize(
        xplane.load(str(path)),
        epoch_spans=() if host_tracer else _epoch_spans(),
    )
    assert summary["window_s"] == 1000e-6
    # union: 100..350, 600..700, 900..950 = 400 us
    assert abs(summary["busy_s"] - 400e-6) < 1e-12
    assert abs(summary["idle_share"] - 0.6) < 1e-9
    assert summary["steps"] == 2 and summary["devices"] == 1
    assert abs(summary["conv_s"] - 300e-6) < 1e-12
    assert summary["category_s"]["convolution"] == summary["conv_s"]
    labels = dict((k, v) for k, v in summary["device_ops"])
    assert abs(labels["convolution:net/conv2/Conv_0"] - 300e-6) < 1e-12
    assert any(k.startswith("loop fusion:bwd:") for k in labels)
    gaps = summary["idle_gaps"]
    assert gaps[0][0] == "bench.host_input.next" and abs(gaps[0][1] - 250e-6) < 1e-12
    assert gaps[1][0] == "bench.dispatch" and abs(gaps[1][1] - 200e-6) < 1e-12
    assert {g[0] for g in gaps[2:]} == {"host.other"}
    assert abs(sum(summary["idle_by_span_s"].values()) - 600e-6) < 1e-12
    assert abs(summary["spans"]["bench.dispatch"] - 190e-6) < 1e-12


def test_union_merges_overlaps():
    assert xplane.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]


def test_a_trace_with_no_device_op_is_an_error(tmp_path):
    import pytest

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, _plane("/device:TPU:0", [("XLA Ops", [])], {})))
    with pytest.raises(ValueError):
        xplane.summarize(xplane.load(str(path)))
