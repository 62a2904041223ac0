"""Inputs from the seed: every random draw of a run starts here, and the
program receives only what these return."""
