"""Library of the on-chip benchmark: everything that is not one cell's data."""
