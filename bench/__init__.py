"""Serving benchmark of the MixFP4 engine on the chip (see BENCHMARK.json)."""
