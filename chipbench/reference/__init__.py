"""Plain float32 reference of the dense decoder, and the seeded weight maker."""
