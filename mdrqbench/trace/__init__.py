"""Reduction of a profiler trace to device busy time, kernel time and gaps."""
