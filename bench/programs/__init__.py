"""One module per CFDlang program the benchmark runs, found by the
``program`` key of a configuration.  Each gives the program's element
inputs, shared operands and outputs by name, its algorithmic work per
element, and its outputs from an arithmetic module (``reference`` in
float64, ``control`` in three bfloat16 passes)."""
