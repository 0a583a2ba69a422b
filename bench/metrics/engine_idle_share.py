"""Share of the traced span in which the device is idle while the host is
inside one of the engine's ``serve.*`` spans, in % (``bench/spans.py``);
the idle time under each span goes to standard error.  None where the
run's context holds no engine trace."""
import sys


def read(ctx):
    et = getattr(ctx, "engine_trace", None)
    if et is None:
        return None
    split = et.idle_split()
    print("engine idle by span: " + ", ".join(
        f"{k} {1e3 * v:.3f} ms" for k, v in split.items()), file=sys.stderr)
    return 100.0 * sum(split.values()) / et.window_s
