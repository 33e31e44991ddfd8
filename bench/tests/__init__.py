"""Tests of the chip benchmark's parts, on the CPU at small sizes."""
