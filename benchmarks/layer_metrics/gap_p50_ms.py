"""Median gap between two consecutive output tokens of one request, over
every gap that ends inside the window: the decode tick plus the host's
step loop.  The host of a one-chip machine runs in two speed states 2.4%
apart that flip within a session (PERF.md section 2), so no bound fits
it: recorded here, and `decode_step_ms` carries the device's part."""
import stats as S


def read(run):
    gaps = run["records"].get("gaps_ms")
    return S.percentile(gaps, 50) if gaps else None
