"""Native acceleration surfaces: the C++ log-emitter source (logemit.cpp,
built and loaded by runtime/native_logemit.py) and the two Pallas kernels
(score_update.py, statically routed on TPU backends; vmem_gather.py,
unrouted — the installed Mosaic does not lower it)."""
