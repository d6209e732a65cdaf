"""95th percentile over every gap between two consecutive output tokens
of one request that ends inside the window.  Stall gaps (an admission
under a full pool holds every slot for 300-600 ms) are 7-10% of all
gaps, so this percentile lies among them and reads how long a stall
lasts; their share and length are what lifts `gap_mean_ms` above the
tick.  It was the end-to-end `gap_p95_ms` until the check of PR 23 read
a spread of 5.2% in both of its sets, more than half of the largest
bound a metric may have (PERF.md section 2): recorded here, not
judged."""
import stats as S


def read(run):
    gaps = run["records"].get("gaps_ms")
    return S.percentile(gaps, 95) if gaps else None
