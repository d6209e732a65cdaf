"""Share of the router's picks that cost nothing: live decode tokens'
assignments to zero-compute experts over all their assignments
(`zero_picks` / (`zero_picks` + `real_picks`), the engine's device
counters since it started; `models/shortcut_moe.py`).  A third with a
fair router over 512 routed and 256 zero-compute columns; higher is less
work a token."""
import scope_paths as SP


def read(run):
    ctr = SP.counters(run)
    if not ctr or "zero_picks" not in ctr:
        return None
    zero, real = float(ctr["zero_picks"]), float(ctr["real_picks"])
    return 100.0 * zero / (zero + real) if zero + real else None
