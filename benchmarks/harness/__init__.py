"""The benchmark's harness: manifest, system under test, loop, check, trace."""
