"""Seeded generators, plain references and deployments, one module per configuration kind."""
