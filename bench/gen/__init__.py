"""The benchmark's own input generators, copied from the program so that a
later change to the program cannot change what the benchmark feeds it."""
