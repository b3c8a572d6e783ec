"""The benchmark's own code: everything under `bench/` is the yardstick,
and the program under test is imported from `src/` at run time."""
