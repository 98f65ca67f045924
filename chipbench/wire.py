"""The TAGGEDFLOW wire lane, encode side only: records -> raw frames.

Copied from `deepflow_tpu/feeder/flowframe.py` (`encode_flowbatch_body`,
`encode_flowbatch_frames`) and `deepflow_tpu/ingest/framing.py`
(`FlowHeader.encode`, `encode_frame`), so that the generator's process
imports nothing of the program. The program's decoder is the system
under test; chipbench/tests pins that it reads what this writes.

Frame = 19-byte header (frame_size u32 BE, msg_type u8, then LE: version
u16, encoder u8, team u32, org u16, reserved u16, agent u16, reserved u8)
+ one message: [len u32 LE][flowframe body]. Body = 5 x u32 LE (magic,
version, rows, tag fields, meter fields) + tag matrix [T, rows] u32 +
meter matrix [rows, M] f32.
"""

from __future__ import annotations

import struct

import numpy as np

_HDR_TAIL = struct.Struct("<HBIHHHB")
_BODY_HDR = struct.Struct("<IIIII")


def encode_frames(tags: np.ndarray, meters: np.ndarray, wire: dict,
                  *, agent_id: int = 1) -> list[bytes]:
    """(tags [T, n] u32, meters [n, M] f32) -> frames of at most
    `rows_per_frame` rows, in row order."""
    t, n = tags.shape
    m = meters.shape[1]
    rows = int(wire["rows_per_frame"])
    frames = []
    for off in range(0, n, rows):
        k = min(rows, n - off)
        body = (
            _BODY_HDR.pack(wire["flowframe_magic"], wire["flowframe_version"],
                           k, t, m)
            + np.ascontiguousarray(tags[:, off:off + k], "<u4").tobytes()
            + np.ascontiguousarray(meters[off:off + k], "<f4").tobytes()
        )
        size = wire["header_len"] + 4 + len(body)
        frames.append(
            struct.pack(">I", size)
            + struct.pack("B", wire["msg_type_taggedflow"])
            + _HDR_TAIL.pack(wire["header_version"], 0, 0, 0, 0, agent_id, 0)
            + struct.pack("<I", len(body))
            + body
        )
    return frames
