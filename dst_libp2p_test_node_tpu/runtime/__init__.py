"""The runtime's modules. Nothing is imported here: `ops/*` make device
constants when they are imported, which starts the device backend, and
`runtime/profiling` and `runtime/compile_cache` have to be importable before
that (cli.main times the backend's start as span `setup/backend`). Import
from the module: `runtime.simulator`, `runtime.summarize`."""
