"""PlaceIT on-chip benchmark: harness, reference, trace reduction."""
