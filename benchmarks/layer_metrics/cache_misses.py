"""Programs this process compiled because the persistent compilation
cache did not hold them, counted up to the instant the window opened
(`_private/compile_cache.stats()`): 0 in every run after a checkout's
first.  Compiles inside the window are printed on an earlier line."""


def read(run):
    at = run["records"].get("cache_at_window")
    return float(at["misses"]) if at else None
