"""Tools that define the benchmark's numbers once, on the chip (the rate
sweep, the control's readings, the trace fixture). The benchmark's own
runs never call them."""
