"""repro — jax_pallas reproduction of "Breaking the Memory Wall for AI
Chip with a New Dimension" grown into a serving/training system."""
