"""The benchmark's own code: specs, traffic, drivers, trace reduction and
checks.  Nothing here is part of the system under test."""
