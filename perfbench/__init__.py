"""The chip benchmark of the BLS verification path (see BENCHMARK.json)."""
