"""Traffic mixes: one generator, one data file of parameters per mix."""
