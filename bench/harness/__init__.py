"""The chip benchmark's general code: traffic, weights, the serving
driver, the reference and its comparison, and the trace reduction."""
