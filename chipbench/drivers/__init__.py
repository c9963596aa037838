"""Window drivers, one module per stage kind, found by the traffic's ``kind``."""
