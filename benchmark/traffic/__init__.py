"""Traffic: data files of parameters and the one generator that reads them."""
