"""Tokens a forward of a slot fixes, from the engine's device counters
over the whole run: `block_tokens_fixed` (positions the denoising steps
fixed) over `block_forwards` (live slot-forwards, the commits among
them).  A block of L positions fixed one a step and then committed reads
L / (L + 1), 0.8 at L = 4; a confidence threshold that is met fixes
several a step and reads higher (2.0 where every block takes one step
and a commit)."""
import scope_paths as SP


def read(run):
    ctr = SP.counters(run)
    if not ctr or not int(ctr.get("block_forwards", 0)):
        return None
    return float(ctr["block_tokens_fixed"]) / float(ctr["block_forwards"])
