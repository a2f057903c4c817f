"""The C++ log emitter only: logemit.cpp, built and loaded by
runtime/native_logemit.py."""
