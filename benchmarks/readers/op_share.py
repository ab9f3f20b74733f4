"""Device time of the ops whose name matches ``pattern`` or whose HLO
opcode is one of ``opcodes``, over device busy time."""

from benchmarks.trace import op_seconds, opcode_seconds


def read(ctx, pattern=None, opcodes=None):
    if ctx.trace is None or not ctx.trace.get("busy_first_s"):
        return None
    seconds = op_seconds(ctx.trace, pattern) if pattern else opcode_seconds(ctx.trace, opcodes)
    return 100.0 * seconds / ctx.trace["busy_first_s"]
