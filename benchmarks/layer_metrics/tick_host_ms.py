"""The host's own work a tick, with no profiler: from the phase clock
the engine keeps always (`engine.stats()["loop"]`), the seconds in
`ctrl` + `tick_dispatch` + `tick_readback` + `emit` + `gauges` over the
ticks, since the engine's `warmup` ended (it starts the clock anew, so no
compile is in it).  While these run the device has nothing queued, but
for one `tick_dispatch` in 64, which holds `TrackedJit`'s sampled fence,
a whole tick long (+ tick / 64).  A MEAN over the whole run (the idle
sample, pre-roll, window and drain), so a run in the host's slow state
reads higher and steps that ran no tick add their `ctrl` and `gauges`;
the phases of an admission (`admit`, `first_token_wait`, `spill_land`)
are left to `admit_stall_ms`, and `tick_ready` is the device's time.
None for a program that keeps no such clock."""
import tick_gap as TG

PHASES = ("ctrl", "tick_dispatch", "tick_readback", "emit", "gauges")


def read(run):
    loop = TG.loop_stats(run)
    if not loop or not loop["ticks"]:
        return None
    print("LOOP (ms a tick over %d ticks, %d steps): " % (
        loop["ticks"], loop["steps"]) + ", ".join(
            "%s %.4f" % (k, 1e3 * v / loop["ticks"])
            for k, v in loop["seconds"].items()), flush=True)
    return 1e3 * sum(loop["seconds"][p] for p in PHASES) / loop["ticks"]
