"""kv_live_share: KV blocks that hold written tokens over the blocks
committed to admitted requests (in use, or promised and not yet drawn),
each summed over the window's untraced engine steps as the step ended, in
%. From the gauges on the program's ``engine.step`` spans."""
from bench import program_spans


def read(run):
    steps = program_spans.window_steps(run)
    if not steps:
        return None
    live = sum(s.gauges.get("kv_blocks_live", 0) for s in steps)
    committed = sum(s.gauges.get("kv_blocks_committed", 0) for s in steps)
    return 100.0 * live / committed if committed else None
