"""A minimal reader of the profiler's ``*.xplane.pb`` (XSpace protobuf):
planes, lines, events with their names, times and stats -- the stats of
an event's METADATA included, which is where the device operations keep
their framework name (``tf_op``) and which ``jax.profiler.ProfileData``
does not show.  Pure Python wire-format decoding of the few fields read.
"""

from __future__ import annotations

import struct


def _varint(buf, i):
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, i
        shift += 7


def _fields(buf):
    """Yield (field number, wire type, value) of one message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 1:
            val, i = buf[i:i + 8], i + 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wt == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt}")
        yield num, wt, val


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names, strings):
    key, value = None, None
    for num, wt, val in _fields(buf):
        if num == 1:
            key = stat_names.get(val, str(val))
        elif num == 2:
            value = struct.unpack("<d", val)[0]
        elif num in (3, 4):
            value = _signed(val) if num == 4 else val
        elif num == 5:
            value = bytes(val).decode("utf-8", "replace")
        elif num == 6:
            value = bytes(val)
        elif num == 7:
            value = strings.get(val, val)
    return key, value


def _map_entry(buf):
    key = value = None
    for num, _wt, val in _fields(buf):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def read(path):
    """[{"name", "lines": [{"name", "events": [(name, start_ns, dur_ns,
    stats)]}]}] of the file."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for num, _wt, pbuf in _fields(space):
        if num != 1:
            continue
        name, lines, ev_meta, st_meta = "", [], {}, {}
        for pn, _w, val in _fields(pbuf):
            if pn == 2:
                name = bytes(val).decode()
            elif pn == 3:
                lines.append(val)
            elif pn == 4:
                k, v = _map_entry(val)
                ev_meta[k] = v
            elif pn == 5:
                k, v = _map_entry(val)
                st_meta[k] = v
        stat_names = {}
        for k, v in st_meta.items():
            for sn, _w, sval in _fields(v):
                if sn == 2:
                    stat_names[k] = bytes(sval).decode()
        # ref_value stats point at a stat metadata's name
        strings = stat_names
        metas = {}
        for k, v in ev_meta.items():
            mname, mstats = "", {}
            for mn, _w, mval in _fields(v):
                if mn == 2:
                    mname = bytes(mval).decode("utf-8", "replace")
                elif mn == 4 and not mname:
                    mname = bytes(mval).decode("utf-8", "replace")
                elif mn == 5:
                    sk, sv = _stat(mval, stat_names, strings)
                    mstats[sk] = sv
            metas[k] = (mname, mstats)
        out_lines = []
        for lbuf in lines:
            lname, t0, events = "", 0, []
            raw = []
            for ln, _w, val in _fields(lbuf):
                if ln == 2:
                    lname = bytes(val).decode()
                elif ln == 3:
                    t0 = val
                elif ln == 4:
                    raw.append(val)
            for ebuf in raw:
                mid, off, dur, stats = 0, 0, 0, None
                for en, _w, val in _fields(ebuf):
                    if en == 1:
                        mid = val
                    elif en == 2:
                        off = val
                    elif en == 3:
                        dur = val
                    elif en == 4:
                        sk, sv = _stat(val, stat_names, strings)
                        stats = stats or {}
                        stats[sk] = sv
                mname, mstats = metas.get(mid, ("", {}))
                merged = dict(mstats, **stats) if stats else mstats
                events.append((mname, t0 + off // 1000, dur // 1000, merged))
            out_lines.append({"name": lname, "events": events})
        planes.append({"name": name, "lines": out_lines})
    return planes
