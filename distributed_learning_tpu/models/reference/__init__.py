"""Plain f32 references of the models the chip benchmark runs: the
oracles the CPU tests and the benchmark's ``correct`` compare against.
Imported where they are used, never by the package's ``__init__``."""
