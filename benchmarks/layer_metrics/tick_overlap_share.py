"""How often the engine's one-deep pipeline engages: 100 x the ticks
dispatched while another tick was still in flight (`overlapped`) over
all the ticks dispatched (`ticks`), from the counts the engine keeps
always (`engine.stats()["loop"]`).  A WHOLE-RUN count, since the
engine's `warmup` ended (the idle sample, pre-roll, window and drain),
as `tick_host_ms` is: the harness reads no `loop` at the window's ends.
The window's own share, counted from the `in_flight=` argument of the
`llm_engine.tick_dispatch` spans that lie inside it, is printed beside
it.  What is left of 100 is the first tick after the engine read its
tick in flight back out of turn: an engine that stood empty, and
whatever else settles (the loop's `settles`, by cause, printed too).
None for a program that keeps no such count (before PR 41)."""
import program_spans as PS
import tick_gap as TG


def read(run):
    loop = TG.loop_stats(run)
    if not loop or not loop.get("ticks") or "overlapped" not in loop:
        return None
    prog = PS.load(run)
    flying = [int(s[3]["in_flight"]) > 0
              for s in (PS.in_window(prog, run["window"],
                                     "llm_engine.tick_dispatch")
                        if prog is not None else ())
              if "in_flight" in s[3]]
    print("OVERLAP %d of %d ticks dispatched with one in flight (in the "
          "window: %d of %d); settles by cause: %s" % (
              loop["overlapped"], loop["ticks"], sum(flying), len(flying),
              loop.get("settles")), flush=True)
    return 100.0 * loop["overlapped"] / loop["ticks"]
