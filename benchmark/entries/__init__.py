"""One module per entry point of the program's CLI that a cell may run.

A configuration's file says `"entry": "<name>"` and harness/manifest.py
finds `<name>.py` here by that name; a file that says nothing runs DEFAULT.
benchmark/README.md, "An entry point: new files only", states what a module
has to have (harness/manifest.ENTRY_PARTS checks it at load).
"""

DEFAULT = "run"
