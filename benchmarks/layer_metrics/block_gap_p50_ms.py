"""Median time between two consecutive BLOCKS of one stream landing, on
the host's clock (the benchmark's own `time.monotonic()` in `on_token`):
a model that generates by blocks emits a block's tokens together, so of
a stream's gaps all but one a block are 0 and the median gap says
nothing; this is the block's own period, the denoising forwards and the
commit.  Token j of a request with a prompt of P tokens sits at position
P + j and opens a block where that is a multiple of `block_length`; a
block's landing is its first token's time, and a gap counts where it
ENDS inside the window, whichever request it belongs to (as
`gap_mean_ms` counts its gaps)."""
import stats as S


def read(run):
    L = run["config"].get("block_length")
    rec = run["records"]
    if not L or "window" not in rec:
        return None
    w0, w1 = rec["window"]
    gaps = []
    for r in rec.get("recs", ()):
        P = len(r.req.prompt)
        lands = [t for j, t in enumerate(r.times)
                 if j == 0 or (P + j) % int(L) == 0]
        gaps += [(b - a) * 1e3 for a, b in zip(lands, lands[1:])
                 if w0 <= b < w1]
    return S.median(gaps) if gaps else None
