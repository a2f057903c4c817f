"""The program's own record of its process (ISSUE 40:
`dst_libp2p_test_node_tpu.runtime.profiling.process_record()`), read in the
benchmark's process after the window: seconds between two of its marks, or
one field of the compile ledger (everything jax traced,
lowered, compiled or loaded up to the end of the process's first turn, the
warm-up experiment). Once a process, so "per traced experiment" does not
apply: the traced window and `ctx` are not read. A program without the
record (the parent of the PR that added it), or a record that lacks what is
asked for, gives None."""


def record():
    try:
        from dst_libp2p_test_node_tpu.runtime import profiling
    except ImportError:
        return None
    make = getattr(profiling, "process_record", None)
    return make() if make is not None else None


def read(ctx, between=None, field=None):
    rec = record()
    if not rec:
        return None
    if between is not None:
        marks = rec.get("marks", {})
        start, end = (marks.get(name) for name in between)
        return None if start is None or end is None else end - start
    return rec.get("compile", {}).get("setup", {}).get(field)
