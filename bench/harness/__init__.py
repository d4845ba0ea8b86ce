"""The benchmark's own machinery: traffic, driving, trace reduction, checks."""
