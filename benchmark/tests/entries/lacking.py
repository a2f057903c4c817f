"""An entry module that has one part of the six: what
harness/manifest.load_entry says of it is what test_entries.py reads."""


def invocation(cell, seed, out_dir):
    return ["lacking"], {}
